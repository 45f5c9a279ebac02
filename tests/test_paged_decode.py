"""Paged KV-cache serving tests (ISSUE 19): the block-pool allocator
invariants (randomized churn), the paged cache ops against numpy
goldens, the paged flash-decode kernel vs its oracle in interpret mode,
the paged DecodeEngine (bit-exact vs the slot ring, kill switch,
backpressure, resize), disaggregated prefill/decode co-residency under
the scope proof, speculative-decoding exactness, the
``decode-cache-unpaged`` lint, and the kv-pool telemetry + trace leg."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.observability as obs
import paddle_tpu.observability.metrics as om
from paddle_tpu.observability import tracing as tr
from paddle_tpu.ops.pallas import paged_flash_decode as PFD
from paddle_tpu.serving import (BlockAllocator, DecodeEngine,
                                GenerationConfig, KVPoolExhausted,
                                PredictorServer, SpeculativeDecoder,
                                blocks_needed, build_block_table,
                                ngram_draft, paged_kv_enabled)
from paddle_tpu.static_analysis.verifier import VerifyError
from paddle_tpu.tools import trace as trace_cli
from test_serving_decode import TinyModel


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    fluid.unique_name.switch()
    for var in ("PADDLE_TPU_TELEMETRY", "PADDLE_TPU_TELEMETRY_DIR",
                "PADDLE_TPU_TELEMETRY_FLUSH", "PADDLE_TPU_TRACING",
                "PADDLE_TPU_STRICT_SYNC", "PADDLE_TPU_PAGED_KV",
                "PADDLE_TPU_PAGED_BLOCK_LEN",
                "PADDLE_TPU_PAGED_MIN_BYTES"):
        monkeypatch.delenv(var, raising=False)
    obs.reset_telemetry()
    yield
    obs.reset_telemetry()


class PagedTinyModel(TinyModel):
    """TinyModel plus the paged builders — the same deterministic
    next-token chain through paged_kv_cache_prefill/write and
    paged_flash_decode (attention folded in at zero weight, so any
    block-routing corruption still poisons the logits)."""

    def build_prefill_paged(self, prompt, plen, table, caches):
        L = prompt.shape[1]
        pf = fluid.layers.cast(prompt, "float32")
        emb = self._embed(fluid.layers.reshape(pf, [L]), L)
        x = fluid.layers.reshape(emb, [1, 1, L, 4])
        k, v = caches[0]
        fluid.layers.paged_kv_cache_prefill(k, x, plen, table)
        fluid.layers.paged_kv_cache_prefill(v, x, plen, table)
        return self._prefill_logits(pf, plen, L)

    def build_step_paged(self, cur, cursors, tables, caches):
        S = cur.shape[0]
        cf = fluid.layers.cast(cur, "float32")
        emb = self._embed(cf, S)
        x = fluid.layers.reshape(emb, [S, 1, 4])
        k, v = caches[0]
        fluid.layers.paged_kv_cache_write(k, x, cursors, tables,
                                          per_row=True)
        fluid.layers.paged_kv_cache_write(v, x, cursors, tables,
                                          per_row=True)
        att = fluid.layers.paged_flash_decode(x, k, v, cursors, tables,
                                              per_row=True)
        return self._step_logits(cf, att, S)


def _engine(model=None, slots=2, max_new=4, name="pg", **kw):
    return DecodeEngine(
        model if model is not None else PagedTinyModel(), slots=slots,
        prompt_buckets=(8,),
        config=GenerationConfig(max_new_tokens=max_new),
        place=fluid.CPUPlace(), name=name, **kw)


def _chain(prompt, n):
    """TinyModel's greedy continuation: next token = last + 1."""
    return [prompt[-1] + 1 + i for i in range(n)]


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------


class TestBlockAllocator:
    def test_helpers(self):
        assert blocks_needed(0, 8) == 0
        assert blocks_needed(1, 8) == 1
        assert blocks_needed(8, 8) == 1
        assert blocks_needed(9, 8) == 2
        np.testing.assert_array_equal(build_block_table([4, 2], 4),
                                      [4, 2, -1, -1])
        np.testing.assert_array_equal(build_block_table([], 3),
                                      [-1, -1, -1])

    def test_deterministic_order_and_all_or_nothing(self):
        pool = BlockAllocator(4, 8)
        assert pool.allocate(2) == [0, 1]
        assert pool.allocate(1) == [2]
        assert not pool.can_allocate(2)
        with pytest.raises(KVPoolExhausted):
            pool.allocate(2)  # all-or-nothing: list untouched
        assert pool.num_free == 1
        pool.free([1])
        assert pool.allocate(2) == [1, 3]  # LIFO: 1 came back on top

    def test_double_free_and_foreign_ids_rejected(self):
        pool = BlockAllocator(2, 8)
        got = pool.allocate(1)
        pool.free(got)
        with pytest.raises(ValueError):
            pool.free(got)  # double-free
        with pytest.raises(ValueError):
            pool.free([7])  # never owned by anyone

    def test_randomized_churn_conserves_and_never_double_assigns(self):
        """Satellite 5: a seeded admit/retire schedule — a block id is
        owned by at most one request, and free + live always sums to
        the pool size."""
        rng = np.random.RandomState(1234)
        pool = BlockAllocator(17, 4)
        live = {}  # rid -> blocks
        rid = 0
        for _ in range(500):
            if rng.rand() < 0.55 or not live:
                want = blocks_needed(int(rng.randint(1, 30)), 4)
                if pool.can_allocate(want):
                    got = pool.allocate(want)
                    assert len(set(got)) == len(got)
                    live[rid] = got
                    rid += 1
                else:
                    with pytest.raises(KVPoolExhausted):
                        pool.allocate(want)
            else:
                victim = list(live)[int(rng.randint(len(live)))]
                pool.free(live.pop(victim))
            owned = [b for bs in live.values() for b in bs]
            assert len(set(owned)) == len(owned)  # no double-assign
            assert pool.num_free + len(owned) == pool.num_blocks
        for bs in live.values():
            pool.free(bs)
        assert pool.num_free == pool.num_blocks  # nothing leaked


# ---------------------------------------------------------------------------
# paged cache ops vs numpy goldens
# ---------------------------------------------------------------------------


def _run(main, feed, fetch):
    exe = fluid.Executor(fluid.CPUPlace())
    return exe.run(main, feed=feed, fetch_list=fetch)


class TestPagedOps:
    N, H, BL, D = 6, 2, 4, 3

    def _cache_feed(self, rng):
        return rng.randn(self.N, self.H, self.BL,
                         self.D).astype("float32")

    def test_write_routes_through_table_and_drops_unmapped(self):
        rng = np.random.RandomState(0)
        cache_np = self._cache_feed(rng)
        x_np = rng.randn(3, self.H, self.D).astype("float32")
        # stream 0 at cursor 5 -> table[1]=4, offset 1; stream 1 at
        # cursor 2 -> table[0]=2, offset 2; stream 2 unmapped (-1 row)
        cursors = np.array([5, 2, 0], dtype="int32")
        tables = np.array([[1, 4], [2, -1], [-1, -1]], dtype="int32")
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            cache = fluid.layers.data(
                "cache", shape=[self.N, self.H, self.BL, self.D],
                dtype="float32", append_batch_size=False)
            x = fluid.layers.data("x", shape=[3, self.H, self.D],
                                  dtype="float32",
                                  append_batch_size=False)
            cur = fluid.layers.data("cur", shape=[3], dtype="int32",
                                    append_batch_size=False)
            tab = fluid.layers.data("tab", shape=[3, 2], dtype="int32",
                                    append_batch_size=False)
            out = fluid.layers.paged_kv_cache_write(
                cache, x, cur, tab, per_row=True, in_place=False)
        got, = _run(main, {"cache": cache_np, "x": x_np,
                           "cur": cursors, "tab": tables}, [out])
        want = cache_np.copy()
        want[4, :, 1, :] = x_np[0]  # cursor 5 = block idx 1, offset 1
        want[2, :, 2, :] = x_np[1]  # cursor 2 = block idx 0, offset 2
        np.testing.assert_array_equal(got, want)  # -1 row dropped

    def test_prefill_scatters_only_real_rows(self):
        rng = np.random.RandomState(1)
        cache_np = np.zeros((self.N, self.H, self.BL, self.D),
                            dtype="float32")
        L = 6
        x_np = rng.randn(1, self.H, L, self.D).astype("float32")
        tables = np.array([3, 1], dtype="int32")
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            cache = fluid.layers.data(
                "cache", shape=[self.N, self.H, self.BL, self.D],
                dtype="float32", append_batch_size=False)
            x = fluid.layers.data("x", shape=[1, self.H, L, self.D],
                                  dtype="float32",
                                  append_batch_size=False)
            ln = fluid.layers.data("ln", shape=[1], dtype="int32",
                                   append_batch_size=False)
            tab = fluid.layers.data("tab", shape=[2], dtype="int32",
                                    append_batch_size=False)
            out = fluid.layers.paged_kv_cache_prefill(
                cache, x, ln, tab, in_place=False)
        got, = _run(main, {"cache": cache_np, "x": x_np,
                           "ln": np.array([5], dtype="int32"),
                           "tab": tables}, [out])
        want = cache_np.copy()
        want[3, :, :, :] = x_np[0, :, 0:4, :]  # rows 0..3 -> block 3
        want[1, :, 0, :] = x_np[0, :, 4, :]    # row 4 -> block 1
        # rows >= plen (the padded tail) must NOT land anywhere
        np.testing.assert_array_equal(got, want)

    def test_gather_matches_ring_layout(self):
        rng = np.random.RandomState(2)
        import jax.numpy as jnp

        cache = rng.randn(5, 2, 4, 3).astype("float32")
        table = np.array([[4, 0, -1], [2, 3, 1]], dtype="int32")
        got = np.asarray(PFD.gather_paged_cache(
            jnp.asarray(cache), jnp.asarray(table)))
        assert got.shape == (2, 2, 12, 3)
        np.testing.assert_array_equal(got[0, :, 0:4], cache[4])
        np.testing.assert_array_equal(got[0, :, 4:8], cache[0])
        np.testing.assert_array_equal(got[1, :, 0:4], cache[2])
        np.testing.assert_array_equal(got[1, :, 4:8], cache[3])
        np.testing.assert_array_equal(got[1, :, 8:12], cache[1])


# ---------------------------------------------------------------------------
# paged kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------


class TestPagedKernelParity:
    @pytest.mark.parametrize("lens_kind", ["full", "ragged", "shallow"])
    def test_kernel_matches_reference(self, monkeypatch, lens_kind):
        """Interpret-mode paged kernel (block-table-indirect DMA +
        online softmax) vs the gather-then-ring-oracle composite:
        <= 1e-5 with a shuffled pool and part-unmapped tables."""
        import jax.numpy as jnp

        monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
        monkeypatch.setenv("PADDLE_TPU_DECODE_MIN_T", "1")
        rng = np.random.RandomState(0)
        S, H, D, BL, MB, N = 2, 2, 64, 16, 8, 20
        q = jnp.asarray(rng.randn(S, H, D).astype("float32"))
        kc = jnp.asarray(rng.randn(N, H, BL, D).astype("float32"))
        vc = jnp.asarray(rng.randn(N, H, BL, D).astype("float32"))
        perm = rng.permutation(N)
        table = np.full((S, MB), -1, dtype="int32")
        table[0, :MB] = perm[:MB]
        table[1, :3] = perm[MB:MB + 3]  # short allocation, -1 tail
        lens = {"full": [MB * BL, 3 * BL],
                "ragged": [37, 41],
                "shallow": [1, 2]}[lens_kind]
        lens = jnp.asarray(lens, jnp.int32)
        table = jnp.asarray(table)
        from paddle_tpu.ops.pallas import use_pallas
        assert use_pallas()[0], "interpret mode must engage the kernel"
        o_kernel = PFD.paged_flash_decode(q, kc, vc, lens, table)
        o_ref = PFD.paged_decode_reference(q, kc, vc, lens, table)
        np.testing.assert_allclose(o_kernel, o_ref, rtol=1e-5,
                                   atol=1e-5)

    def test_block_len_divides_max_len(self):
        assert 64 % PFD.paged_block_len(4, 64) == 0
        assert 48 % PFD.paged_block_len(4, 48) == 0
        assert PFD.paged_block_len(4, 8) <= 8


# ---------------------------------------------------------------------------
# the paged engine
# ---------------------------------------------------------------------------


PROMPTS = [[3, 5, 7], [2], [1, 2, 3, 4]]


def _generate_all(eng, prompts=PROMPTS):
    futs = [eng.submit(p) for p in prompts]
    return [f.result(timeout=60)[0] for f in futs]


class TestPagedEngine:
    def test_paged_matches_ring_bit_exactly(self):
        with _engine(TinyModel(), name="ring") as ring:
            assert not ring.stats()["paged"]
            ring_toks = _generate_all(ring)
        fluid.unique_name.switch()
        with _engine(name="paged") as paged:
            st = paged.stats()
            assert st["paged"] and st["block_len"] >= 1
            assert st["kv_blocks_free"] == st["kv_blocks_total"]
            assert _generate_all(paged) == ring_toks
            # equal HBM by default: the pool is exactly the ring's rows
            assert paged.cache_bytes == ring.cache_bytes

    def test_kill_switch_restores_ring_path(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PAGED_KV", "0")
        assert not paged_kv_enabled()
        with _engine(name="ks") as eng:  # paged-capable model
            assert not eng.stats()["paged"]
            assert _generate_all(eng) == [
                _chain(p, 4) for p in PROMPTS]

    def test_explicit_paged_without_builders_raises(self):
        with pytest.raises(ValueError, match="build_prefill_paged"):
            _engine(TinyModel(), paged=True, auto_start=False)

    def test_block_len_must_divide_depth(self):
        with pytest.raises(ValueError, match="divide"):
            _engine(block_len=5, auto_start=False)  # max_len 32

    def test_pool_backpressure_not_failure(self):
        """Six requests through a pool that only fits four: the
        admission loop waits for retirements instead of failing."""
        with _engine(slots=4, num_blocks=4, block_len=8,
                     name="small") as eng:
            futs = [eng.submit([i + 1]) for i in range(6)]
            for i, f in enumerate(futs):
                assert f.result(timeout=60)[0] == _chain([i + 1], 4)
            st = eng.stats()
            assert st["kv_blocks_free"] == st["kv_blocks_total"]

    def test_oversized_request_rejected_up_front(self):
        with _engine(slots=1, num_blocks=1, block_len=8,
                     name="cap") as eng:
            with pytest.raises(ValueError, match="pool"):
                # bucket 8 + 4 new tokens needs 2 blocks; pool holds 1
                eng.submit([1, 2, 3, 4, 5, 6, 7])

    def test_resize_rebuilds_pool(self):
        with _engine(name="rsz") as eng:
            assert eng.submit([2]).result(timeout=60)[0] == _chain(
                [2], 4)
            eng.resize(3)
            assert eng.stats()["kv_blocks_total"] == 3 * eng.max_blocks
            assert eng.submit([2]).result(timeout=60)[0] == _chain(
                [2], 4)

    def test_churn_matches_ring_and_conserves_pool(self):
        """Seeded admit/generate/retire churn (satellite 5): the paged
        engine's outputs stay bit-identical to the slot ring's, and the
        pool drains back to fully free."""
        rng = np.random.RandomState(7)
        prompts = [list(rng.randint(1, 8, size=rng.randint(1, 6)))
                   for _ in range(12)]
        with _engine(TinyModel(), name="cr") as ring:
            ring_toks = _generate_all(ring, prompts)
        fluid.unique_name.switch()
        with _engine(slots=3, num_blocks=6, block_len=8,
                     name="cp") as paged:
            assert _generate_all(paged, prompts) == ring_toks
            st = paged.stats()
            assert st["kv_blocks_free"] == st["kv_blocks_total"]
            assert st["completed"] == len(prompts)


# ---------------------------------------------------------------------------
# disaggregated prefill/decode
# ---------------------------------------------------------------------------


class TestDisaggregation:
    def test_disagg_requires_paged(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PAGED_KV", "0")
        with pytest.raises(ValueError, match="paged"):
            _engine(disaggregate=True, auto_start=False)

    def test_same_tokens_with_handoff_metrics(self):
        with _engine(name="dz", disaggregate=True) as eng:
            assert eng.stats()["disaggregated"]
            assert _generate_all(eng) == [_chain(p, 4) for p in PROMPTS]
        assert om.counter("serving_kv_handoffs_total",
                          tenant="dz").value == len(PROMPTS)
        assert om.counter("serving_kv_handoff_blocks_total",
                          tenant="dz").value > 0

    def test_server_proves_isolation_and_certifies_both_families(
            self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_STRICT_SYNC", "1")
        eng = _engine(name="gen", disaggregate=True, auto_start=False)
        server = PredictorServer({"gen": eng})
        try:
            for name, cert in server.certificates.items():
                assert cert.ok, (name,
                                 [str(d) for d in cert.diagnostics])
            assert "gen" in server.certificates
            assert any(n.startswith("gen.prefill")
                       for n in server.certificates)
            # the prefill/decode cache overlap is a DECLARED handoff:
            # downgraded to INFO, never ERROR
            diags = server.placement_diags
            assert all(d.severity < 40 for d in diags)
            assert any(d.check == "scope-handoff" for d in diags)
            toks, _ = server.submit("gen", [3, 5, 7]).result(timeout=60)
            assert toks == _chain([3, 5, 7], 4)
        finally:
            server.close()

    def test_undeclared_overlap_still_rejected(self):
        e1 = _engine(TinyModel(), name="dup", auto_start=False)
        fluid.unique_name.switch()
        e2 = _engine(TinyModel(), name="dup", auto_start=False)
        try:
            with pytest.raises(VerifyError):
                PredictorServer({"a": e1, "b": e2})
        finally:
            e1.close()
            e2.close()


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------


class TestSpeculative:
    def _spec(self, draft, k=3, max_new=8, name="sp", eos_id=None):
        return SpeculativeDecoder(
            PagedTinyModel(), draft=draft, k=k,
            config=GenerationConfig(max_new_tokens=max_new,
                                    eos_id=eos_id),
            prompt_buckets=(8,), place=fluid.CPUPlace(), name=name)

    def test_ngram_draft_lookup(self):
        # most recent earlier occurrence of the last token wins
        assert ngram_draft([5, 1, 2, 5, 9, 5], 3) == [9, 5, 5]
        assert ngram_draft([1, 2, 3], 2) == [3, 3]  # no match: repeat

    def test_perfect_draft_accepts_everything(self):
        with self._spec(lambda ctx, k: _chain(ctx, k),
                        name="sp1") as dec:
            toks, info = dec.generate([3, 5, 7])
        assert toks == _chain([3, 5, 7], 8)
        assert info["acceptance_rate"] == 1.0
        assert info["rounds"] == 2  # prefill token + 2 x (k+1)
        assert om.gauge("spec_acceptance_rate",
                        tenant="sp1").value == 1.0
        assert om.counter("spec_tokens_proposed_total",
                          tenant="sp1").value == info["proposed"]

    def test_hostile_draft_is_still_exact(self):
        with self._spec(lambda ctx, k: [0] * k, name="sp0") as dec:
            toks, info = dec.generate([3, 5, 7])
        assert toks == _chain([3, 5, 7], 8)  # exactness, not luck
        assert info["acceptance_rate"] == 0.0
        assert info["rounds"] == 7  # one emitted token per round

    def test_draft_model_tenant_is_exact_and_isolated(self):
        from paddle_tpu.static_analysis.concurrency import \
            prove_scope_isolation

        with self._spec(PagedTinyModel(), name="spd") as dec:
            toks, info = dec.generate([3, 5, 7])
            progs = dec.coresident_programs()
            labels = [l for l, _p, _t in progs]
            _fp, diags = prove_scope_isolation(
                [p for _l, p, _t in progs], labels=labels)
            assert not [d for d in diags if d.severity >= 40], \
                [str(d) for d in diags]
        assert any(l.startswith("spd.draft") for l in labels)
        assert toks == _chain([3, 5, 7], 8)
        assert info["acceptance_rate"] == 1.0

    def test_eos_inside_accepted_window_truncates(self):
        with self._spec(lambda ctx, k: _chain(ctx, k), max_new=10,
                        name="spe", eos_id=8) as dec:
            toks, _info = dec.generate([5])
        assert toks == [6, 7, 8]

    def test_greedy_only(self):
        with pytest.raises(ValueError, match="greedy"):
            SpeculativeDecoder(
                PagedTinyModel(),
                config=GenerationConfig(strategy="top_k"),
                prompt_buckets=(8,), place=fluid.CPUPlace())


# ---------------------------------------------------------------------------
# the decode-cache-unpaged lint
# ---------------------------------------------------------------------------


def _ring_step_program(slots=4, heads=8, tmax=512, dh=64):
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cursors = fluid.layers.data("cursors", shape=[slots],
                                    dtype="int32",
                                    append_batch_size=False)
        k = main.global_block().create_var(
            name="kc", shape=[slots, heads, tmax, dh],
            dtype="float32", persistable=True)
        x = fluid.layers.fill_constant([slots, heads, dh], "float32",
                                       1.0)
        fluid.layers.kv_cache_write(k, x, cursors, per_row=True)
        out = fluid.layers.reduce_sum(
            fluid.layers.flash_decode(x, k, k, cursors, per_row=True))
    return main, out


def _unpaged_hits(main, out):
    rep = main.analyze(targets=[out.name])
    return [d for d in rep.diagnostics
            if d.check == "decode-cache-unpaged"]


class TestDecodeCacheUnpagedLint:
    def test_flags_large_ring_cache_with_fragmentation_hint(self):
        from paddle_tpu.static_analysis.diagnostics import Severity

        hits = _unpaged_hits(*_ring_step_program())
        assert len(hits) == 1
        d = hits[0]
        assert d.severity == Severity.INFO  # advisory, never blocking
        assert "slot-ring" in d.message and "block_len" in d.message
        assert "build_prefill_paged" in d.hint

    def test_kill_switch_reason(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PAGED_KV", "0")
        hits = _unpaged_hits(*_ring_step_program())
        assert len(hits) == 1
        assert "kill switch" in hits[0].message

    def test_small_cache_below_floor_is_quiet(self, monkeypatch):
        small = _ring_step_program(slots=1, heads=1, tmax=32, dh=4)
        assert not _unpaged_hits(*small)
        monkeypatch.setenv("PADDLE_TPU_PAGED_MIN_BYTES", "1")
        small = _ring_step_program(slots=1, heads=1, tmax=32, dh=4)
        assert len(_unpaged_hits(*small)) == 1

    def test_paged_program_is_quiet_and_analyzable(self):
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            cursors = fluid.layers.data("cursors", shape=[4],
                                        dtype="int32",
                                        append_batch_size=False)
            tables = fluid.layers.data("tables", shape=[4, 32],
                                       dtype="int32",
                                       append_batch_size=False)
            k = main.global_block().create_var(
                name="kp", shape=[128, 8, 16, 64], dtype="float32",
                persistable=True)
            x = fluid.layers.fill_constant([4, 8, 64], "float32", 1.0)
            fluid.layers.paged_kv_cache_write(k, x, cursors, tables,
                                              per_row=True)
            out = fluid.layers.reduce_sum(
                fluid.layers.paged_flash_decode(x, k, k, cursors,
                                                tables))
        rep = main.analyze(targets=[out.name])
        assert not [d for d in rep.diagnostics
                    if d.check == "decode-cache-unpaged"]
        assert not rep.errors, [str(d) for d in rep.diagnostics]


# ---------------------------------------------------------------------------
# telemetry + trace
# ---------------------------------------------------------------------------


class TestPagedTelemetry:
    def test_kv_pool_gauges_track_the_pool(self):
        with _engine(name="tg") as eng:
            eng.submit([3, 5, 7]).result(timeout=60)
            total = eng.stats()["kv_blocks_total"]
        assert om.gauge("kv_blocks_total", tenant="tg").value == total
        assert om.gauge("kv_blocks_free", tenant="tg").value == total
        assert om.gauge("kv_pool_occupancy", tenant="tg").value == 0.0

    def test_kv_handoff_trace_leg(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR",
                           str(tmp_path / "telemetry"))
        monkeypatch.setenv("PADDLE_TPU_TELEMETRY_FLUSH", "1")
        obs.reset_telemetry()
        with _engine(name="th", disaggregate=True) as eng:
            eng.submit([3]).result(timeout=60)
        tr.get_tracer().flush()
        recs = tr.read_traces(str(tmp_path / "telemetry"))
        by_name = {}
        for r in recs:
            by_name.setdefault(r["name"], []).append(r)
        assert "serving.kv_handoff" in by_name
        root = by_name["serving.request"][0]
        # the handoff hangs off the request root: the third TTFT leg
        # (prefill -> handoff wait -> first decode step)
        assert by_name["serving.kv_handoff"][0]["parent"] == \
            root["span"]
        # the leg's statistic, from the request and its handoff alone:
        # whether a sibling that ran beside the handoff covers it on the
        # critical path is the machine's load, not the engine's doing
        leg = [r for r in recs
               if r["name"] in ("serving.request", "serving.kv_handoff")]
        stats = trace_cli.serving_stats(trace_cli.group_traces(leg))
        assert "kv_handoff_p50_ms" in stats
