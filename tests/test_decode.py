"""Autoregressive decoding tests (ISSUE 14): the ring-buffer KV cache
ops, the flash-decode kernel vs its XLA oracle (interpret mode on CPU),
the sampling ops, the recompile-free ``decode_loop`` contract (jit-cache
entry count flat across generated lengths + the zero-sync certificate
under ``PADDLE_TPU_STRICT_SYNC=1``), the autotune ``decode`` family's
``PADDLE_TPU_AUTOTUNE=0`` bit-exact fallback, and the
``decode-shape-unbucketed`` lint check."""

import importlib
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.executor import Scope, scope_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")
if EXAMPLES not in sys.path:
    sys.path.insert(0, EXAMPLES)

FD = importlib.import_module("paddle_tpu.ops.pallas.flash_decode")


def _run(main, startup, feed, fetch):
    exe = fluid.Executor(fluid.TPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        return exe.run(main, feed=feed, fetch_list=fetch)


# ---------------------------------------------------------------------------
# KV-cache ops
# ---------------------------------------------------------------------------


class TestKVCacheOps:
    def test_shared_cursor_write_and_ring_wrap(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.registry import get_op_def

        op = get_op_def("kv_cache_write")
        B, H, T, D = 2, 2, 4, 3
        cache = jnp.zeros((B, H, T, D), jnp.float32)
        x = jnp.ones((B, H, D), jnp.float32)
        out = op.fn(None, {}, cache, x, jnp.asarray([1], jnp.int32))
        assert float(out[:, :, 1, :].min()) == 1.0
        assert float(jnp.abs(out[:, :, 0, :]).max()) == 0.0
        # cursor T+1 wraps to position 1 (ring semantics)
        wrapped = op.fn(None, {}, cache, 2 * x,
                        jnp.asarray([T + 1], jnp.int32))
        assert float(wrapped[:, :, 1, :].min()) == 2.0

    def test_per_row_write_each_slot_its_own_depth(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.registry import get_op_def

        op = get_op_def("kv_cache_write")
        B, H, T, D = 3, 1, 8, 2
        cache = jnp.zeros((B, H, T, D), jnp.float32)
        x = jnp.ones((B, H, D), jnp.float32)
        cursors = jnp.asarray([0, 3, 5], jnp.int32)
        out = np.asarray(op.fn(None, {"per_row": True}, cache, x,
                               cursors))
        for b, pos in enumerate([0, 3, 5]):
            assert out[b, 0, pos].min() == 1.0
            mask = np.ones(T, bool)
            mask[pos] = False
            assert np.abs(out[b, 0, mask]).max() == 0.0

    def test_prefill_slot_routes_one_row(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.registry import get_op_def

        op = get_op_def("kv_cache_prefill")
        S, H, T, D, L = 3, 1, 8, 2, 4
        cache = jnp.zeros((S, H, T, D), jnp.float32)
        x = jnp.ones((1, H, L, D), jnp.float32)
        out = np.asarray(op.fn(None, {}, cache, x,
                               jnp.asarray([1], jnp.int32)))
        assert out[1, 0, :L].min() == 1.0
        assert np.abs(out[0]).max() == 0.0 and np.abs(out[2]).max() == 0.0
        assert np.abs(out[1, 0, L:]).max() == 0.0


# ---------------------------------------------------------------------------
# flash-decode kernel vs XLA oracle
# ---------------------------------------------------------------------------


class TestFlashDecodeKernel:
    @pytest.mark.parametrize("t,lens_kind", [(256, "full"),
                                             (512, "ragged"),
                                             (512, "shallow")])
    def test_kernel_matches_reference(self, monkeypatch, t, lens_kind):
        """Interpret-mode kernel vs the XLA composite: ≤1e-5 relative
        (the documented oracle tolerance), including cursors well short
        of the cache capacity (the masked-block skip path)."""
        import jax.numpy as jnp

        monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
        monkeypatch.setenv("PADDLE_TPU_DECODE_MIN_T", "1")
        rng = np.random.RandomState(0)
        B, H, D = 2, 2, 64
        q = jnp.asarray(rng.randn(B, H, D).astype("float32"))
        k = jnp.asarray(rng.randn(B, H, t, D).astype("float32"))
        v = jnp.asarray(rng.randn(B, H, t, D).astype("float32"))
        lens = {"full": jnp.asarray([t, t], jnp.int32),
                "ragged": jnp.asarray([7, 300], jnp.int32),
                "shallow": jnp.asarray([1, 2], jnp.int32)}[lens_kind]
        use, _ = FD.use_pallas()
        assert use, "interpret mode must engage the kernel path"
        o_kernel = FD.flash_decode(q, k, v, lens)
        o_ref = FD.decode_reference(q, k, v, lens)
        np.testing.assert_allclose(o_kernel, o_ref, rtol=1e-5,
                                   atol=1e-5)

    def test_reference_empty_cache_is_zeros_not_nan(self):
        import jax.numpy as jnp

        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 2, 8).astype("float32"))
        k = jnp.asarray(rng.randn(1, 2, 16, 8).astype("float32"))
        v = jnp.asarray(rng.randn(1, 2, 16, 8).astype("float32"))
        out = np.asarray(FD.decode_reference(q, k, v,
                                             jnp.asarray([0], jnp.int32)))
        assert np.all(np.isfinite(out)) and np.abs(out).max() == 0.0


class TestAutotuneDefaults:
    def test_autotune_off_restores_hand_set_defaults(self, monkeypatch,
                                                     tmp_path):
        """PADDLE_TPU_AUTOTUNE=0 must restore the hand-set 512/256
        bit-exactly even when the cache holds a measured winner."""
        from paddle_tpu import autotune

        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "cache.json"))
        monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
        monkeypatch.delenv("PADDLE_TPU_DECODE_BLOCK_K", raising=False)
        monkeypatch.delenv("PADDLE_TPU_DECODE_MIN_T", raising=False)
        autotune.record_decode_min_t(1024)
        assert FD.decode_min_t() == 1024  # the cache decision wins...
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        assert FD.decode_min_t() == FD.DEFAULT_MIN_T  # ...until opt-out
        assert FD.decode_block_k(2048, 64) == FD.DEFAULT_BLOCK_K
        # block size still respects divisibility against short caches
        assert 128 % FD.decode_block_k(128, 64) == 0

    def test_env_caps_beat_cache(self, monkeypatch, tmp_path):
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "cache.json"))
        monkeypatch.setenv("PADDLE_TPU_DECODE_MIN_T", "64")
        assert FD.decode_min_t() == 64


# ---------------------------------------------------------------------------
# sampling ops
# ---------------------------------------------------------------------------


def _sample_once(strategy, logits, step_val, **kw):
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    B, V = logits.shape
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[B, V], dtype="float32",
                              append_batch_size=False)
        step = fluid.layers.data("step", shape=[1], dtype="int32",
                                 append_batch_size=False)
        out = fluid.layers.sampling(x, strategy=strategy, step=step,
                                    **kw)
    res = _run(main, startup,
               {"x": logits, "step": np.asarray([step_val], "int32")},
               [out])
    return np.asarray(res[0])


class TestSampling:
    def test_greedy_is_argmax(self):
        rng = np.random.RandomState(0)
        logits = rng.randn(4, 32).astype("float32")
        out = _sample_once("greedy", logits, 0)
        np.testing.assert_array_equal(out, logits.argmax(-1))

    def test_top_k_stays_in_top_k_and_step_decorrelates(self):
        rng = np.random.RandomState(1)
        logits = rng.randn(8, 64).astype("float32")
        k = 5
        topk = np.argsort(-logits, axis=-1)[:, :k]
        draws = {}
        for step in range(3):
            out = _sample_once("top_k", logits, step, k=k,
                               temperature=1.0, seed=7)
            for b in range(len(out)):
                assert out[b] in topk[b]
            draws[step] = out.tolist()
            # replay at the same step is bit-exact
            again = _sample_once("top_k", logits, step, k=k,
                                 temperature=1.0, seed=7)
            assert again.tolist() == draws[step]
        # the step fold must decorrelate: not every step identical
        assert len({tuple(v) for v in draws.values()}) > 1

    def test_top_p_head_token_always_reachable(self):
        # p -> 0 keeps only the head of the nucleus: exactly greedy
        rng = np.random.RandomState(2)
        logits = rng.randn(6, 40).astype("float32")
        out = _sample_once("top_p", logits, 3, p=1e-9, temperature=1.0,
                           seed=3)
        np.testing.assert_array_equal(out, logits.argmax(-1))

    def test_top_p_respects_nucleus(self):
        # one dominant token (mass > p) => nucleus is that token alone
        logits = np.full((3, 16), -10.0, "float32")
        logits[:, 5] = 10.0
        out = _sample_once("top_p", logits, 1, p=0.9, temperature=1.0,
                           seed=0)
        assert out.tolist() == [5, 5, 5]


# ---------------------------------------------------------------------------
# the recompile-free generation contract (gpt_small end to end)
# ---------------------------------------------------------------------------


def _generate(exe, scope, batch, prompt_len, max_new, keep, seed=0):
    import gpt_small

    fluid.unique_name.switch()
    main, startup, feeds, tokens, gen_len = gpt_small.build_program(
        gpt_small.GPT_TINY, batch, prompt_len, max_new)
    # the jit cache is keyed by id(program): keep the programs alive so
    # a later build can't reuse a dead id and alias a cache entry
    keep.append((main, startup, tokens, gen_len))
    rng = np.random.RandomState(seed)
    feed = gpt_small.make_fake_prompt(batch, prompt_len,
                                      gpt_small.GPT_TINY, rng)
    with scope_guard(scope):
        exe.run(startup)
        out = exe.run(main, feed=feed, fetch_list=[tokens, gen_len])
    return main, np.asarray(out[0]), np.asarray(out[1])


class TestDecodeLoopContract:
    def test_jit_cache_flat_across_generated_lengths(self, monkeypatch):
        """The tentpole: the jit cache holds the same number of entries
        whether the loop generates 4 tokens or 16 — no per-step (or
        per-length) recompile — and re-feeding different prompts adds
        nothing."""
        import gpt_small

        monkeypatch.setenv("PADDLE_TPU_STRICT_SYNC", "1")
        exe = fluid.Executor(fluid.TPUPlace())
        keep = []
        base = len(exe._cache)
        _main, toks, _ = _generate(exe, Scope(), 2, 8, 4, keep)
        short = len(exe._cache) - base
        assert toks.shape == (2, 4)
        scope = Scope()
        _main, toks, _ = _generate(exe, scope, 2, 8, 16, keep, seed=1)
        long = len(exe._cache) - base - short
        assert toks.shape == (2, 16)
        assert long == short, (
            "per-generation jit entries grew with generated length: "
            "%d vs %d" % (long, short))
        # warm re-runs with fresh prompts (same program, same scope):
        # zero new entries
        main, _startup, tokens, gen_len = keep[-1]
        warm = len(exe._cache)
        with scope_guard(scope):
            for seed in (4, 5):
                feed = gpt_small.make_fake_prompt(
                    2, 8, gpt_small.GPT_TINY,
                    np.random.RandomState(seed))
                exe.run(main, feed=feed, fetch_list=[tokens, gen_len])
        assert len(exe._cache) == warm

    def test_zero_sync_certificate_over_decode_program(self,
                                                       monkeypatch):
        """The generation program passes the PR-10 zero-sync certificate
        with strict-sync promotion on: the while-op decode loop adds no
        host sync to the hot path."""
        import gpt_small

        from paddle_tpu.static_analysis.concurrency import \
            certify_zero_sync

        monkeypatch.setenv("PADDLE_TPU_STRICT_SYNC", "1")
        fluid.unique_name.switch()
        main, startup, feeds, tokens, gen_len = gpt_small.build_program(
            gpt_small.GPT_TINY, 2, 8, 4)
        main._serving_hot_loop = True
        cert = certify_zero_sync(main,
                                 targets=[tokens.name, gen_len.name],
                                 label="decode")
        assert cert.ok, "\n".join(str(d) for d in cert.diagnostics)

    def test_kv_cache_matches_naive_full_recompute(self):
        """Equivalence oracle: greedy decoding through the ring cache
        produces exactly the naive recompute-everything tokens.  A
        short max_len keeps the naive arm's all-Tmax-per-step graphs
        cheap — bench.py's --child decode runs the Tmax=512 A/B."""
        import gpt_small

        cfg = gpt_small.GPTConfig(max_len=32)
        toks_kv, _glen, _t, _r = gpt_small.run_generate(
            lambda: gpt_small.build_program(cfg, 2, 8, 6), cfg, 2, 8, 6)
        toks_nv, _glen, _t, _r = gpt_small.run_generate(
            lambda: gpt_small.build_naive_program(cfg, 2, 8, 6),
            cfg, 2, 8, 6)
        np.testing.assert_array_equal(toks_kv, toks_nv)

    def test_eos_early_exit_pads_with_eos(self):
        """A vocabulary rigged so the decode loop hits eos row-by-row:
        gen_len counts real tokens, finished rows keep emitting eos
        until every row is done, and positions past the global early
        exit keep the initial zero fill (slice with gen_len)."""
        V, eos = 16, 3
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            first = fluid.layers.data("first", shape=[2], dtype="int32",
                                      append_batch_size=False)
            plen = fluid.layers.data("plen", shape=[1], dtype="int32",
                                     append_batch_size=False)

            def step(cur, cursor, i):
                # next = cur + 1 (one-hot logits), so rows march to eos
                nxt = fluid.layers.elementwise_add(
                    cur, fluid.layers.fill_constant([2], "int32", 1))
                oh = fluid.layers.one_hot(
                    fluid.layers.reshape(nxt, [2, 1]), V)
                return fluid.layers.cast(oh, "float32")

            tokens, gen_len = fluid.layers.decode_loop(
                step, first, plen, max_new_tokens=8, eos_id=eos)
        out = _run(main, startup,
                   {"first": np.asarray([0, 2], "int32"),
                    "plen": np.asarray([1], "int32")},
                   [tokens, gen_len])
        toks, glen = np.asarray(out[0]), np.asarray(out[1])
        # row 0: 0,1,2,3(eos) -> 4 real tokens; row 1: 2,3(eos) -> 2
        assert glen.tolist() == [4, 2]
        assert toks[0, :4].tolist() == [0, 1, 2, 3]
        assert toks[1, :2].tolist() == [2, 3]
        # row 1 finished early: it keeps writing eos until row 0
        # finishes at step 4, which is also the loop's early exit —
        # slots past that keep the initial zero fill
        assert toks[1, 2:4].tolist() == [eos, eos]
        assert toks[0, 4:].tolist() == [0] * 4
        assert toks[1, 4:].tolist() == [0] * 4


# ---------------------------------------------------------------------------
# decode-shape-unbucketed lint
# ---------------------------------------------------------------------------


class TestDecodeShapeLint:
    def _naive_concat_loop(self):
        """The anti-pattern: a while loop growing its carried KV by
        concat every step (the reference DecoderBase shape regime)."""
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            kv = fluid.layers.data("kv", shape=[2, 4, 8],
                                   dtype="float32",
                                   append_batch_size=False)
            step = fluid.layers.data("x", shape=[2, 1, 8],
                                     dtype="float32",
                                     append_batch_size=False)
            i = fluid.layers.fill_constant([1], "int32", 0)
            limit = fluid.layers.fill_constant([1], "int32", 4)
            cond = fluid.layers.less_than(i, limit)
            w = fluid.layers.While(cond)
            with w.block():
                grown = fluid.layers.concat([kv, step], axis=1)
                fluid.layers.assign(grown, output=kv)
                fluid.layers.increment(i, value=1, in_place=True)
                fluid.layers.less_than(i, limit, cond=cond)
            out = fluid.layers.reduce_sum(kv)
        return main, out

    def test_positive_flags_growing_carry(self):
        main, out = self._naive_concat_loop()
        report = main.analyze(targets=[out.name])
        hits = [d for d in report.diagnostics
                if d.check == "decode-shape-unbucketed"]
        assert hits, "concat-grown loop carry must be flagged"
        assert "ring-buffer" in (hits[0].hint or "")

    def test_negative_gpt_small_is_clean(self):
        import gpt_small

        fluid.unique_name.switch()
        main, startup, feeds, tokens, gen_len = gpt_small.build_program(
            gpt_small.GPT_TINY, 2, 8, 4)
        report = main.analyze(targets=[tokens.name, gen_len.name])
        assert not [d for d in report.diagnostics
                    if d.check == "decode-shape-unbucketed"]
        assert not report.errors
