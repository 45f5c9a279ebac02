"""Serving-layer tests: shape buckets, padded-batch bit-exactness,
continuous batching, SLA shedding, fairness, backpressure, the
multi-tenant placement/zero-sync gates, the bounded content-keyed
FeedCache, telemetry + monitor wiring, and the ``tools.serve`` CLI."""

import json
import time

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.observability as obs
import paddle_tpu.observability.metrics as om
from paddle_tpu import pipeline as pl
from paddle_tpu import serving
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.framework import Operator
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.serving import buckets as bk
from paddle_tpu.static_analysis.verifier import VerifyError


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    fluid.unique_name.switch()
    for var in ("PADDLE_TPU_SERVING_BUCKETS",
                "PADDLE_TPU_SERVING_BUCKET_CAP",
                "PADDLE_TPU_FEED_CACHE_CAP",
                "PADDLE_TPU_STRICT_SYNC"):
        monkeypatch.delenv(var, raising=False)
    obs.reset_telemetry()
    yield
    obs.reset_telemetry()


IN_DIM = 6


def _save_model(dirname, seed=0, out_dim=3):
    """Build + save a tiny fc inference model; returns its dir."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[IN_DIM], dtype="float32")
        h = fluid.layers.fc(x, size=8, act="relu")
        out = fluid.layers.fc(h, size=out_dim, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        np.random.seed(seed)
        exe.run(startup)
        fluid.io.save_inference_model(str(dirname), ["x"], [out], exe,
                                      main_program=main)
    return str(dirname)


def _predictor(dirname):
    return AnalysisPredictor(AnalysisConfig(model_dir=dirname))


def _rows(rng, n):
    return rng.standard_normal((n, IN_DIM)).astype("float32")


class _DummyPred:
    """Predictor-shaped stub for gate tests (never actually run)."""

    def __init__(self, program, outputs):
        self.program = program
        self._outputs = outputs

    def get_input_names(self):
        return []

    def get_output_names(self):
        return list(self._outputs)

    def run_async(self, feed):  # pragma: no cover - gates fire first
        raise AssertionError("should be gated before any run")


def _named_mlp(prefix, train=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(prefix + "_x", shape=[4], dtype="float32")
        h = fluid.layers.fc(
            x, size=4, param_attr=fluid.ParamAttr(name=prefix + ".w"),
            bias_attr=fluid.ParamAttr(name=prefix + ".b"))
        if train:
            loss = fluid.layers.mean(h)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, h.name


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

class TestBuckets:
    def test_parse_and_resolve_precedence(self, monkeypatch):
        assert bk.parse_buckets("8,1,4,4") == (1, 4, 8)
        monkeypatch.setenv(bk.BUCKETS_ENV, "2,16")
        assert bk.resolve_buckets() == (2, 16)          # env wins
        assert bk.resolve_buckets(explicit="1,3") == (1, 3)  # arg wins
        monkeypatch.delenv(bk.BUCKETS_ENV)
        assert bk.resolve_buckets() == bk.DEFAULT_BUCKETS

    def test_cap_is_enforced_not_silently_truncated(self, monkeypatch):
        monkeypatch.setenv(bk.BUCKET_CAP_ENV, "2")
        with pytest.raises(ValueError, match="cap"):
            bk.resolve_buckets(explicit="1,2,4")
        assert bk.resolve_buckets(explicit="1,8") == (1, 8)

    def test_derive_pow2_rounds_and_thins_to_cap(self):
        assert bk.derive_buckets([1, 3, 3, 5], cap=8) == (1, 4, 8)
        derived = bk.derive_buckets(range(1, 200), cap=4)
        assert len(derived) == 4
        assert derived[0] == 1 and derived[-1] == 256

    def test_bucket_for_and_padding(self):
        b = bk.ShapeBuckets((2, 4))
        assert b.bucket_for(1) == 2 and b.bucket_for(3) == 4
        assert b.bucket_for(5) is None
        a = np.arange(6, dtype="float32").reshape(3, 2)
        padded = b.pad_rows(a, 3, 4)
        assert padded.shape == (4, 2)
        assert np.array_equal(padded[:3], a)
        assert np.array_equal(padded[3], a[2])  # last row repeated
        outs = b.slice_rows([padded, np.float32(7.0)], 1, 3, 4)
        assert np.array_equal(outs[0], a[1:3])
        assert outs[1] == np.float32(7.0)  # non-batch output broadcast

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            bk.parse_buckets("0,2")
        with pytest.raises(ValueError):
            bk.parse_buckets("")

    def test_seq_axis_default_behavior_unchanged(self, monkeypatch):
        monkeypatch.delenv(bk.SEQ_BUCKETS_ENV, raising=False)
        assert bk.resolve_buckets(explicit="1,4") == (1, 4)  # no pair
        b = bk.ShapeBuckets((2, 4))
        assert b.seq_sizes is None
        with pytest.raises(ValueError, match="sequence-length"):
            b.bucket_for_seq(8)

    def test_seq_axis_resolution_and_precedence(self, monkeypatch):
        got = bk.resolve_buckets(explicit="1,4", seq="128,32")
        assert got == ((1, 4), (32, 128))
        monkeypatch.setenv(bk.SEQ_BUCKETS_ENV, "64,256")
        assert bk.resolve_buckets(explicit="1,4") == ((1, 4), (64, 256))
        # explicit seq beats env; observed lengths derive when neither
        assert bk.resolve_buckets(explicit="1", seq="16") == ((1,), (16,))
        monkeypatch.delenv(bk.SEQ_BUCKETS_ENV)
        got = bk.resolve_buckets(explicit="1",
                                 seq_observed=[30, 60, 100])
        assert got == ((1,), (32, 64, 128))

    def test_seq_axis_bucket_for_and_pad(self):
        b = bk.ShapeBuckets((1, 2), seq_sizes=(32, 128))
        assert b.seq_sizes == (32, 128)
        assert b.bucket_for_seq(7) == 32
        assert b.bucket_for_seq(33) == 128
        assert b.bucket_for_seq(129) is None
        ids = np.arange(20, dtype="int32").reshape(2, 10)
        padded = b.pad_seq(ids, 10, 32)
        assert padded.shape == (2, 32)
        assert np.array_equal(padded[:, :10], ids)
        assert (padded[:, 10:] == 0).all()
        assert b.pad_seq(ids, 10, 10) is ids  # no-op when full

    def test_seq_axis_grid_cap_enforced(self, monkeypatch):
        monkeypatch.setenv(bk.BUCKET_CAP_ENV, "2")
        with pytest.raises(ValueError, match="grid"):
            bk.resolve_buckets(explicit="1,2", seq="8,16,32,64,128")


# ---------------------------------------------------------------------------
# padded-bucket bit-exactness (the satellite-3 contract)
# ---------------------------------------------------------------------------

def _assert_served_rows(pred, group, bucket, got):
    """The server's contract for requests that rode one batch: padding
    and slicing change nothing.  Each request's rows are bit-exact to the
    same rows run through the predictor at the bucket's own shape
    (concatenated, padded by ``ShapeBuckets.pad_rows``' rule, sliced
    back), and equal the unpadded run within float32 rounding — a CPU
    matmul is not bit-invariant in the batch size."""
    x = np.concatenate(group, axis=0)
    at_bucket = pred.run(
        {"x": bk.ShapeBuckets.pad_rows(x, len(x), bucket)})[0]
    off = 0
    for rows, out in zip(group, got):
        assert out[0].shape == (len(rows), at_bucket.shape[1])
        assert np.array_equal(out[0], at_bucket[off:off + len(rows)])
        np.testing.assert_allclose(out[0], pred.run({"x": rows})[0],
                                   rtol=1e-6)
        off += len(rows)


class TestPaddedCorrectness:
    @pytest.mark.parametrize("max_in_flight", [1, 2])
    @pytest.mark.parametrize("fusion", ["0", "1"])
    def test_padded_results_bit_exact_vs_unpadded(
            self, tmp_path, monkeypatch, max_in_flight, fusion):
        monkeypatch.setenv("PADDLE_TPU_FUSION", fusion)
        pred = _predictor(_save_model(tmp_path / "m"))
        rng = np.random.RandomState(0)
        server = serving.PredictorServer(
            {"t": pred}, max_in_flight=max_in_flight, buckets=(4,),
            auto_start=False)
        xs = [_rows(rng, n) for n in (1, 3, 2, 1)]
        reqs = [server.submit("t", {"x": x}) for x in xs]
        server.start()
        got = [r.result(timeout=60) for r in reqs]
        server.close()
        # 1+3 filled the single bucket of 4; 2+1 were padded into it
        assert server.dispatch_log == [("t", 4, 4), ("t", 4, 3)]
        _assert_served_rows(pred, xs[:2], 4, got[:2])
        _assert_served_rows(pred, xs[2:], 4, got[2:])

    def test_coalesced_multi_request_batch_slices_correctly(
            self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        rng = np.random.RandomState(1)
        server = serving.PredictorServer(
            {"t": pred}, buckets=(8,), auto_start=False)
        x1, x2 = _rows(rng, 2), _rows(rng, 3)
        r1 = server.submit("t", {"x": x1})
        r2 = server.submit("t", {"x": x2})
        server.start()
        o1, o2 = r1.result(timeout=60), r2.result(timeout=60)
        server.close()
        # both rode one padded batch ...
        assert len(server.dispatch_log) == 1
        assert server.dispatch_log[0] == ("t", 8, 5)
        # ... and each got exactly its own rows back
        _assert_served_rows(pred, [x1, x2], 8, [o1, o2])

    def test_jit_cache_bounded_by_bucket_count(self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        rng = np.random.RandomState(2)
        server = serving.PredictorServer({"t": pred}, buckets=(1, 2, 4),
                                         auto_start=False)
        server.warmup({"t": {"x": _rows(rng, 1)}})
        warm = len(pred._exe._cache)
        assert warm <= 3
        server.start()
        reqs = [server.submit("t", {"x": _rows(rng, 1 + i % 4)})
                for i in range(12)]
        for r in reqs:
            r.result(timeout=60)
        server.close()
        # mixed row counts never minted a new jit signature
        assert len(pred._exe._cache) == warm


# ---------------------------------------------------------------------------
# scheduling: fairness, SLA shedding, backpressure
# ---------------------------------------------------------------------------

class TestScheduling:
    def test_round_robin_fairness_across_tenants(self, tmp_path):
        pa = _predictor(_save_model(tmp_path / "a", seed=0))
        pb = _predictor(_save_model(tmp_path / "b", seed=1))
        server = serving.PredictorServer({"a": pa, "b": pb},
                                         buckets=(2,), auto_start=False)
        rng = np.random.RandomState(3)
        reqs = [server.submit("a", {"x": _rows(rng, 1)})
                for _ in range(6)]
        reqs += [server.submit("b", {"x": _rows(rng, 1)})
                 for _ in range(2)]
        server.start()
        for r in reqs:
            r.result(timeout=60)
        server.close()
        # b's lone batch is NOT starved behind a's three: round-robin
        # puts it second
        tenants = [t for t, _, _ in server.dispatch_log]
        assert tenants[0] == "a" and tenants[1] == "b"

    def test_sla_shed_and_survivors(self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, buckets=(2,),
                                         auto_start=False)
        rng = np.random.RandomState(4)
        dead = server.submit("t", {"x": _rows(rng, 1)}, sla_ms=-5,
                             request_id="late")
        live = server.submit("t", {"x": _rows(rng, 1)})
        server.start()
        with pytest.raises(serving.DeadlineExceededError,
                           match="late"):
            dead.result(timeout=60)
        assert live.result(timeout=60)[0].shape == (1, 3)
        server.close()
        stats = server.stats()
        assert stats["shed"] == 1 and stats["completed"] == 1
        assert stats["shed_rate"] == 0.5

    def test_backpressure_bounded_queue_rejects(self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, queue_cap=3,
                                         buckets=(4,), auto_start=False)
        rng = np.random.RandomState(5)
        reqs = [server.submit("t", {"x": _rows(rng, 1)})
                for _ in range(3)]
        with pytest.raises(serving.QueueFullError, match="backpressure"):
            server.submit("t", {"x": _rows(rng, 1)})
        server.start()
        for r in reqs:
            r.result(timeout=60)
        server.close()
        assert server.stats()["rejected"] == 1

    def test_submit_after_close_and_unknown_tenant(self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, auto_start=False)
        rng = np.random.RandomState(6)
        with pytest.raises(KeyError):
            server.submit("nope", {"x": _rows(rng, 1)})
        server.close()
        with pytest.raises(serving.ServerClosedError):
            server.submit("t", {"x": _rows(rng, 1)})


# ---------------------------------------------------------------------------
# dispatcher-crash containment
# ---------------------------------------------------------------------------

class TestDispatcherCrash:
    def test_crash_fails_pending_journals_and_poisons_submit(
            self, tmp_path, monkeypatch):
        from paddle_tpu.observability import journal as oj

        tdir = tmp_path / "tel"
        monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tdir))
        obs.reset_telemetry()
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, buckets=(2,),
                                         auto_start=False)

        def boom():
            raise RuntimeError("scheduler bug")

        # crash OUTSIDE the per-batch guards: the thread itself dies
        monkeypatch.setattr(server, "_pick_batch_locked", boom)
        rng = np.random.RandomState(11)
        r1 = server.submit("t", {"x": _rows(rng, 1)})
        r2 = server.submit("t", {"x": _rows(rng, 1)})
        server.start()
        # blocked clients get a typed verdict, never a silent hang
        with pytest.raises(serving.DispatcherCrashedError,
                           match="scheduler bug"):
            r1.result(timeout=60)
        with pytest.raises(serving.DispatcherCrashedError):
            r2.result(timeout=60)
        # the server stays dead: submit/start raise the same error
        with pytest.raises(serving.DispatcherCrashedError):
            server.submit("t", {"x": _rows(rng, 1)})
        with pytest.raises(serving.DispatcherCrashedError):
            server.start()
        assert server.stats()["failed"] == 2
        # ... and the crash is journaled urgent as dispatcher-died
        died = [e for e in oj.read_journal(str(tdir))
                if e["kind"] == "dispatcher-died"]
        assert died and died[0]["failed_requests"] == 2
        assert "scheduler bug" in died[0]["reason"]
        server.close()

    def test_batch_failure_does_not_kill_the_dispatcher(self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, buckets=(2,),
                                         auto_start=False)
        orig = pred.run_async
        calls = []

        def flaky(feed):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("one bad batch")
            return orig(feed)

        pred.run_async = flaky
        rng = np.random.RandomState(12)
        r1 = server.submit("t", {"x": _rows(rng, 2)})
        server.start()
        with pytest.raises(RuntimeError, match="one bad batch"):
            r1.result(timeout=60)
        # the per-batch guard contained it: the server still serves
        r2 = server.submit("t", {"x": _rows(rng, 1)})
        assert r2.result(timeout=60)[0].shape == (1, 3)
        server.close()


# ---------------------------------------------------------------------------
# enqueue-time validation (satellite 2)
# ---------------------------------------------------------------------------

class TestEnqueueValidation:
    def test_submit_attributes_bad_shape_to_request_id(self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, auto_start=False)
        bad = np.zeros((1, IN_DIM + 2), dtype="float32")
        with pytest.raises(ValueError) as ei:
            server.submit("t", {"x": bad}, request_id="req-7")
        msg = str(ei.value)
        assert "req-7" in msg and "declares" in msg
        server.close()

    def test_submit_rejects_oversized_and_scalar_feeds(self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, buckets=(2,),
                                         auto_start=False)
        rng = np.random.RandomState(7)
        with pytest.raises(ValueError, match="largest bucket"):
            server.submit("t", {"x": _rows(rng, 3)})
        with pytest.raises(ValueError, match="batch dim"):
            server.submit("t", {"x": np.float32(1.0)})
        server.close()

    def test_run_batches_validates_at_enqueue_with_request_ids(
            self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        good = [np.zeros((2, IN_DIM), dtype="float32")]
        bad = [np.zeros((2, IN_DIM + 1), dtype="float32")]
        with pytest.raises(ValueError) as ei:
            list(pred.run_batches([good, bad, good], max_in_flight=2,
                                  request_ids=["g1", "b2", "g3"]))
        msg = str(ei.value)
        # attributed to the offending request, with the data-layer
        # declaration — not a raw jit shape error K steps later
        assert "b2" in msg and "declares" in msg

    def test_run_batches_without_ids_names_batch_index(self, tmp_path):
        pred = _predictor(_save_model(tmp_path / "m"))
        bad = [np.zeros((2, IN_DIM + 1), dtype="float32")]
        with pytest.raises(ValueError, match="batch #0"):
            list(pred.run_batches([bad]))


# ---------------------------------------------------------------------------
# construction-time gates
# ---------------------------------------------------------------------------

class TestGates:
    def test_scope_overlap_blocks_placement(self):
        a, a_out = _named_mlp("m", train=True)   # writes m.w / m.b
        b, b_out = _named_mlp("m")               # reads m.w / m.b
        with pytest.raises(VerifyError, match="scope-overlap"):
            serving.PredictorServer(
                {"train": _DummyPred(a, [a_out]),
                 "serve": _DummyPred(b, [b_out])},
                auto_start=False)

    def test_disjoint_tenants_pass_and_record_certificates(self):
        a, a_out = _named_mlp("a")
        b, b_out = _named_mlp("b")
        server = serving.PredictorServer(
            {"a": _DummyPred(a, [a_out]), "b": _DummyPred(b, [b_out])},
            auto_start=False)
        assert server.certificates["a"].ok
        assert server.certificates["b"].ok
        assert a._serving_hot_loop and b._serving_hot_loop
        server.close()

    def test_host_sync_op_blocks_hot_loop(self):
        main, out = _named_mlp("s")
        blk = main.global_block()
        blk.ops.append(Operator(blk, "save", {"X": [out]}, {},
                                {"file_path": "/tmp/x"}))
        with pytest.raises(VerifyError, match="sync"):
            serving.PredictorServer({"s": _DummyPred(main, [out])},
                                    auto_start=False)

    def test_no_verify_skips_gates(self):
        a, a_out = _named_mlp("m", train=True)
        b, b_out = _named_mlp("m")
        server = serving.PredictorServer(
            {"train": _DummyPred(a, [a_out]),
             "serve": _DummyPred(b, [b_out])},
            verify=False, auto_start=False)
        assert server.certificates["train"] is not None
        server.close()


# ---------------------------------------------------------------------------
# FeedCache: bounded LRU + content-shape keying (satellite 1)
# ---------------------------------------------------------------------------

class TestFeedCache:
    def test_content_keyed_hit_on_equal_copy(self):
        cache = pl.FeedCache(cap=4)
        a = np.arange(12, dtype="float32").reshape(3, 4)
        cache.put("x", a, "dev")
        # a fresh array with equal content hits (the serving pattern:
        # per-request arrays are never identical objects)
        assert cache.get("x", a.copy()) == "dev"
        assert cache.get("x", a) == "dev"  # identity fast path

    def test_no_false_hit_on_different_content_or_name(self):
        cache = pl.FeedCache(cap=4)
        a = np.zeros((2, 2), dtype="float32")
        cache.put("x", a, "dev")
        assert cache.get("x", np.ones((2, 2), dtype="float32")) is None
        assert cache.get("y", a.copy()) is None
        assert cache.get("x", np.zeros((4,), dtype="float32")) is None

    def test_fingerprint_collision_cannot_corrupt(self):
        cache = pl.FeedCache(cap=4)
        a = np.zeros((256,), dtype="float32")
        cache.put("x", a, "dev")
        # mutate an element the strided 64-sample fingerprint skips:
        # same key, different content — the full compare must miss
        b = a.copy()
        b[1] = 99.0
        assert cache._key("x", a) == cache._key("x", b)
        assert cache.get("x", b) is None

    def test_lru_eviction_bounded_and_counted(self):
        obs.reset_telemetry()
        cache = pl.FeedCache(cap=2)
        arrs = [np.full((2,), i, dtype="float32") for i in range(3)]
        for i, a in enumerate(arrs):
            cache.put("x", a, "dev%d" % i)
        assert len(cache) == 2
        assert cache.get("x", arrs[0]) is None   # oldest evicted
        assert cache.get("x", arrs[2]) == "dev2"
        assert om.counter("feed_cache_evictions_total").value == 1

    def test_cap_from_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FEED_CACHE_CAP", "1")
        cache = pl.FeedCache()
        cache.put("x", np.zeros(2, dtype="float32"), "d0")
        cache.put("x", np.ones(2, dtype="float32"), "d1")
        assert len(cache) == 1

    def test_in_place_mutation_still_misses(self):
        cache = pl.FeedCache(cap=4)
        a = np.arange(8, dtype="float32")
        cache.put("x", a, "dev")
        a += 1.0
        assert cache.get("x", a) is None


# ---------------------------------------------------------------------------
# telemetry + monitor wiring
# ---------------------------------------------------------------------------

class TestServingTelemetry:
    def test_metrics_flow_into_monitor_status_and_alerts(
            self, tmp_path):
        from paddle_tpu.observability.exporters import \
            write_metrics_snapshot
        from paddle_tpu.tools.monitor import check_alert, collect_status

        obs.reset_telemetry()
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, buckets=(2,),
                                         auto_start=False)
        rng = np.random.RandomState(8)
        reqs = [server.submit("t", {"x": _rows(rng, 1)})
                for _ in range(4)]
        server.start()
        for r in reqs:
            r.result(timeout=60)
        server.close()

        tdir = tmp_path / "telemetry"
        tdir.mkdir()
        write_metrics_snapshot(str(tdir / "metrics-r0-1.json"))
        status = collect_status(str(tdir))
        assert status["serving_requests"] == 4
        assert status["p50_serving_latency_ms"] > 0
        assert status["p99_serving_latency_ms"] >= \
            status["p50_serving_latency_ms"]
        assert status["serving_throughput_qps"] > 0
        assert status["serving_shed_rate"] == 0.0
        code, _ = check_alert(status, "p99_serving_latency_ms>0")
        assert code == 1  # tripped: any positive latency
        code, _ = check_alert(status, "serving_shed_rate>0")
        assert code == 0
        code, _ = check_alert(status, "p99_serving_latency_ms>99999999")
        assert code == 0

    def test_batch_occupancy_and_padding_counters(self, tmp_path):
        obs.reset_telemetry()
        pred = _predictor(_save_model(tmp_path / "m"))
        server = serving.PredictorServer({"t": pred}, buckets=(4,),
                                         auto_start=False)
        rng = np.random.RandomState(9)
        r = server.submit("t", {"x": _rows(rng, 3)})
        server.start()
        r.result(timeout=60)
        server.close()
        assert om.counter("serving_rows_total").value == 3
        assert om.counter("serving_padded_rows_total").value == 1
        assert om.gauge("serving_batch_occupancy").value == 0.75


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestServeCLI:
    def test_loadgen_json_report(self, tmp_path, capsys):
        from paddle_tpu.tools import serve

        d = _save_model(tmp_path / "m")
        rc = serve.main([d, "--requests", "8", "--qps", "500",
                         "--max-in-flight", "2", "--buckets", "1,2",
                         "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["completed"] == 8
        assert report["p50_ms"] > 0 and report["p99_ms"] > 0
        assert report["qps"] > 0
        assert report["zero_sync"] == {"default": True}
        assert report["jit_entries"]["default"] <= 2

    def test_certify_zero_sync_preflight(self, tmp_path, capsys):
        from paddle_tpu.tools import serve

        d = _save_model(tmp_path / "m")
        rc = serve.main([d, "--certify-zero-sync"])
        assert rc == 0
        assert "PASS" in capsys.readouterr().out

    def test_two_tenants_named(self, tmp_path, capsys):
        from paddle_tpu.tools import serve

        da = _save_model(tmp_path / "a", seed=0)
        fluid.unique_name.switch()
        db = _save_model(tmp_path / "b", seed=1)
        rc = serve.main(["--tenants", "ta=%s,tb=%s" % (da, db),
                        "--requests", "8", "--qps", "500", "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert set(report["tenants"]) == {"ta", "tb"}
        assert report["zero_sync"] == {"ta": True, "tb": True}
