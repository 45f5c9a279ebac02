"""Chaos harness: run a short training loop under an injected fault spec
and exit nonzero unless the run RECOVERS.

Usage::

    python -m paddle_tpu.tools.chaos \
        --steps 9 --spec "nan_grad@step=3;ckpt_write_fail@step=5;worker_kill@step=7"

The driver supervises a training *worker* subprocess (this same module
with ``--worker``) the way a production job controller supervises a
trainer:

* the worker trains a fixed deterministic model, pins the injector step
  each iteration, saves an atomic versioned checkpoint every step, and
  auto-resumes from the latest intact version on boot;
* the driver restarts a killed/hung worker with jittered backoff (up to
  ``--max-restarts``), bounding each incarnation with a wall-clock
  timeout so an injected hang also surfaces;
* after the final incarnation finishes, the driver replays the SAME
  schedule fault-free in-process, *skipping* the steps the guarded
  worker skipped, and demands the final parameter digest match
  bit-for-bit.

Exit status: 0 = recovered and matched; 1 = survived but diverged;
2 = did not survive (restarts exhausted / no completion).

This is the executable form of the ISSUE-2 acceptance scenario — CI runs
it with the spec above; any spec drawn from the
``PADDLE_TPU_FAULT_SPEC`` grammar works.

``--elastic`` runs the ISSUE-12 acceptance scenario instead: an
elastic cluster of ``--elastic-world`` workers trains a shared global
batch, one worker is killed mid-run, and the survivors must re-plan,
reshard and resume IN-PROCESS at the shrunk world size — no restart.
The post-recovery loss curve is diffed against a same-seed oracle run
uninterrupted at the shrunk world size (exit 1 beyond ``--tolerance``),
and the journal must show the
``worker-lost → replan → reshard → resume`` incident chain.

``--quant`` runs the quantized-collective A/B drill (ISSUE-15): twin
same-seed data-parallel training runs where the control reduces
gradients densely and the quant twin pushes every gradient bucket
through the real int8 block-quantized reduction pipeline
(quantize → dequant-sum → requant → dequant, exactly the
``quant/collective.py`` wire math for a 2-rank ring).  Every step the
measured quantization error is checked against the documented error
model and fed to the ``quant_error`` drift gauge; the drill exits 1
unless the two loss curves stay within ``--tolerance`` relative error
AND both converge.
"""

import argparse
import hashlib
import os
import subprocess
import sys
import tempfile
import time

def _force_cpu():
    """Both the worker and the in-process oracle run on CPU: the drill
    verifies recovery logic, and the bit-for-bit digest comparison needs
    one platform on both sides (the env var alone can be ignored when an
    image pins a TPU plugin via jax config)."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass


# deterministic tiny regression problem — the model must be
# dropout-free so a skipped step is exactly "one batch not applied"
_DATA_SEED = 1234
_MODEL_SEED = 77
_BATCH = 16
_FEATS = 4
_HIDDEN = 8
_LR = 0.1


def _build_model():
    import paddle_tpu as fluid

    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = _MODEL_SEED
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[_FEATS], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=_HIDDEN, act="relu")
        p = fluid.layers.fc(h, size=1)
        loss = fluid.layers.reduce_mean(fluid.layers.square(p - y))
        fluid.optimizer.Adam(learning_rate=_LR).minimize(loss)
    return main, startup, loss


def _batches(steps):
    import numpy as np

    rng = np.random.RandomState(_DATA_SEED)
    out = []
    for _ in range(steps):
        xb = rng.randn(_BATCH, _FEATS).astype("float32")
        yb = (xb.sum(axis=1, keepdims=True)
              + 0.1 * rng.randn(_BATCH, 1)).astype("float32")
        out.append((xb, yb))
    return out


def _param_digest(scope, program):
    import numpy as np

    h = hashlib.sha256()
    for v in sorted(program.list_vars(), key=lambda v: v.name):
        if not v.persistable:
            continue
        val = scope.get(v.name)
        if val is None:
            continue
        h.update(v.name.encode())
        h.update(np.ascontiguousarray(np.asarray(val)).tobytes())
    return h.hexdigest()


def _run_worker(args):
    """One trainer incarnation: resume → train → checkpoint each step."""
    import warnings

    import numpy as np  # noqa: F401

    _force_cpu()
    import paddle_tpu as fluid
    from paddle_tpu.resilience import checkpoint, faults, guard

    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    start_step = 0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        info = checkpoint.try_load_latest_checkpoint(
            exe, args.ckpt_dir, main_program=main)
    if info is not None:
        start_step = int(info.state.get("next_step", info.step + 1))
        print("CHAOS_RESUME step=%d from=%s"
              % (start_step, os.path.basename(info.path)), flush=True)
        from paddle_tpu.observability import journal as _journal

        _journal.emit("resume", step=start_step,
                      source=os.path.basename(info.path))

    for k, (xb, yb) in enumerate(_batches(args.steps)):
        if k < start_step:
            continue
        faults.set_step(k)
        skipped_before = guard.stats.skipped_steps
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss])
        skipped = int(guard.stats.skipped_steps > skipped_before)
        print("CHAOS_STEP %d loss=%.8f skipped=%d"
              % (k, float(np.asarray(lv).reshape(())), skipped),
              flush=True)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            checkpoint.save_checkpoint(
                exe, args.ckpt_dir, main_program=main, step=k,
                state={"next_step": k + 1}, retain=3)
    digest = _param_digest(fluid.global_scope(), main)
    print("CHAOS_FINAL params_sha=%s skipped_total=%d"
          % (digest, guard.stats.skipped_steps), flush=True)
    print("CHAOS_OK", flush=True)
    return 0


def _oracle_digest(steps, skip_steps, spec):
    """Fault-free replay in-process of the step the worker compiled, not
    applying the skipped steps — the trajectory the recovered run must
    land on exactly.  The guard's select and the value faults' gate feed
    are part of that step, so the replay keeps both and lets nothing
    fire: XLA's CPU backend does not round two different programs alike
    to the last bit, and what the drill holds to the bit is recovery."""
    import warnings
    from unittest import mock

    _force_cpu()
    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.resilience import faults

    inj = faults.set_fault_spec(spec)
    inj.faults = inj.trace_faults
    for f in inj.faults:
        f.p = 0.0                       # its gate stays cold
    try:
        with mock.patch.dict(os.environ, {"PADDLE_TPU_NAN_GUARD": "1"}), \
                scope_guard(Scope()):               # guarded, as the worker
            main, startup, loss = _build_model()
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for k, (xb, yb) in enumerate(_batches(steps)):
                if k in skip_steps:
                    continue
                faults.set_step(k)
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss])
            return _param_digest(fluid.global_scope(), main)
    finally:
        faults.set_fault_spec("")


# elastic drill: a constant GLOBAL batch sliced by membership index —
# divisible by both the full and the shrunk world, so the global
# gradient (sum of member means / world) is identical at every world
# size and the shrunk-world oracle is comparable within fp tolerance
_GLOBAL_BATCH = 24


def _elastic_batches(steps):
    import numpy as np

    rng = np.random.RandomState(_DATA_SEED)
    out = []
    for _ in range(steps):
        xb = rng.randn(_GLOBAL_BATCH, _FEATS).astype("float32")
        yb = (xb.sum(axis=1, keepdims=True)
              + 0.1 * rng.randn(_GLOBAL_BATCH, 1)).astype("float32")
        out.append((xb, yb))
    return out


def _elastic_feed(batches):
    def make_feed(step, index, world):
        xb, yb = batches[step]
        n = xb.shape[0] // world
        sl = slice(index * n, (index + 1) * n)
        return {"x": xb[sl], "y": yb[sl]}
    return make_feed


def _run_elastic_worker(args):
    """One elastic cluster member: the ElasticTrainer owns the loop —
    worker loss is recovered in here, never by a process restart."""
    import warnings

    import numpy as np

    _force_cpu()
    import paddle_tpu as fluid
    from paddle_tpu.resilience import elastic

    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    batches = _elastic_batches(args.steps)
    delay = float(getattr(args, "step_delay", 0.0) or 0.0)

    def on_step(step, fetches, trainer):
        print("ELASTIC_STEP %d rank=%d index=%d world=%d epoch=%d "
              "loss=%.8f"
              % (step, trainer.rank, trainer.index, trainer.world,
                 trainer.epoch,
                 float(np.asarray(fetches[0]).reshape(()))), flush=True)
        if delay > 0:
            # rejoin drills pace the fleet so a relaunched worker has
            # live steps left to join
            time.sleep(delay)

    trainer = elastic.ElasticTrainer(
        main, startup, exe, rank=args.rank, world=args.world,
        workdir=args.ckpt_dir, fetch_list=[loss.name],
        batch_size=_GLOBAL_BATCH, ckpt_every=1,
        stale_timeout=args.stale_timeout,
        wedge_timeout=args.worker_timeout)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            trainer.run(args.steps, _elastic_feed(batches), on_step,
                        join=bool(getattr(args, "join", False)))
    except elastic.ElasticEvictedError as e:
        print("ELASTIC_EVICTED %s" % e, flush=True)
        return elastic.ELASTIC_EVICTED_EXIT_CODE
    digest = _param_digest(fluid.global_scope(), trainer.train_prog)
    print("ELASTIC_FINAL rank=%d params_sha=%s world=%d epoch=%d"
          % (trainer.rank, digest, trainer.world, trainer.epoch),
          flush=True)
    print("ELASTIC_OK", flush=True)
    return 0


def _elastic_oracle(steps, world):
    """Uninterrupted same-seed trajectory at the shrunk world size,
    simulated in one process through the SAME plan/split/reduce helpers
    the distributed workers run — per-step, per-member losses."""
    import warnings

    _force_cpu()
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.resilience import elastic, faults

    faults.set_fault_spec("")
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    batches = _elastic_batches(steps)
    make_feed = _elastic_feed(batches)
    per_step = []
    with scope_guard(Scope()), warnings.catch_warnings():
        warnings.simplefilter("ignore")
        prog, st, split, _result, _applied = elastic.plan_world(
            main, startup, world, batch_size=_GLOBAL_BATCH)
        exe.run(program=st if st is not None else startup)
        for k in range(steps):
            if split is None:
                out = exe.run(program=prog, feed=make_feed(k, 0, 1),
                              fetch_list=[loss.name])
                per_step.append(
                    [float(np.asarray(out[0]).reshape(()))])
                continue
            ng = len(split.grad_names)
            per_member, member_losses, passthrough = [], [], {}
            for idx in range(world):
                out = exe.run(
                    program=split.head, feed=make_feed(k, idx, world),
                    fetch_list=[loss.name] + split.grad_names
                    + split.passthrough)
                member_losses.append(
                    float(np.asarray(out[0]).reshape(())))
                per_member.append(
                    dict(zip(split.grad_names, out[1:1 + ng])))
                if idx == 0:
                    passthrough = dict(zip(split.passthrough,
                                           out[1 + ng:]))
            reduced = elastic.reduce_gradients(per_member,
                                               split.pre_scale)
            feed = dict(passthrough)
            feed.update(reduced)
            exe.run(program=split.tail, feed=feed, fetch_list=[])
            per_step.append(member_losses)
    return per_step


def _parse_elastic_output(text):
    """{step: (index, world, epoch, loss)} plus final/evicted flags."""
    steps = {}
    final = None
    for line in text.splitlines():
        if line.startswith("ELASTIC_STEP "):
            parts = line.split()
            k = int(parts[1])
            kv = dict(p.split("=") for p in parts[2:])
            steps[k] = (int(kv["index"]), int(kv["world"]),
                        int(kv["epoch"]), float(kv["loss"]))
        elif line.startswith("ELASTIC_FINAL "):
            parts = line.split()
            kv = dict(p.split("=") for p in parts[1:])
            final = kv
    return steps, final


def _run_elastic_driver(args):
    """Spawn the elastic cluster, kill one worker, verify the survivors
    recover in-process and track the shrunk-world oracle."""
    import subprocess as sp

    from paddle_tpu.resilience.faults import KILL_EXIT_CODE

    world = args.elastic_world
    kill_rank = world - 1 if args.kill_rank is None else args.kill_rank
    workdir = args.ckpt_dir or tempfile.mkdtemp(
        prefix="paddle_tpu_elastic_")
    os.makedirs(workdir, exist_ok=True)
    telemetry_dir = args.telemetry_dir \
        or os.path.join(workdir, "telemetry")
    print("chaos[elastic]: world=%d kill rank %d at step %d, %d steps, "
          "workdir=%s" % (world, kill_rank, args.kill_step, args.steps,
                          workdir), flush=True)

    # one traceparent for the whole drill: every worker's spans join
    # this trace, so worker-lost→replan→reshard→resume reconstructs as
    # ONE trace across victim + survivors (tools.trace --elastic)
    from paddle_tpu.observability import tracing as _tracing

    drill_ctx = _tracing.new_trace_context()
    drill_tp = _tracing.format_traceparent(drill_ctx)
    print("chaos[elastic]: trace %s" % drill_ctx.trace_id, flush=True)

    procs, logs = [], []
    for rank in range(world):
        env = dict(os.environ)
        # workers always train on the CPU: a chip belongs to one process,
        # and several workers (or a parent that holds it) cannot share it
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_TPU_TELEMETRY_DIR"] = telemetry_dir
        env["PADDLE_TPU_TRACEPARENT"] = drill_tp
        # drills are short and killed mid-flight: flush every span so
        # the victim's pre-death spans reach disk before the kill
        env.setdefault("PADDLE_TPU_TELEMETRY_FLUSH", "1")
        env.pop("PADDLE_TPU_FAULT_SPEC", None)
        env.pop("PADDLE_TPU_NAN_GUARD", None)
        if rank == kill_rank:
            env["PADDLE_TPU_FAULT_SPEC"] = (
                "worker_kill@step=%d" % args.kill_step)
            env["PADDLE_TPU_FAULT_STATE_FILE"] = os.path.join(
                workdir, "fault_state_r%d.json" % rank)
        cmd = [sys.executable, "-m", "paddle_tpu.tools.chaos",
               "--elastic-worker", "--rank", str(rank),
               "--world", str(world), "--steps", str(args.steps),
               "--ckpt-dir", workdir,
               "--stale-timeout", str(args.stale_timeout),
               "--worker-timeout", str(args.worker_timeout)]
        if args.step_delay:
            cmd += ["--step-delay", str(args.step_delay)]
        logf = open(os.path.join(workdir, "worker-r%d.log" % rank),
                    "w+")
        logs.append(logf)
        procs.append(sp.Popen(cmd, env=env, stdout=logf,
                              stderr=sp.STDOUT))

    deadline = time.time() + args.worker_timeout

    def _abort(msg):
        print("chaos[elastic]: FAIL — %s" % msg, flush=True)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for logf in logs:
            logf.close()
        return 2

    if args.rejoin:
        from paddle_tpu.observability.journal import read_journal

        victim = procs[kill_rank]
        while victim.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if victim.returncode != KILL_EXIT_CODE:
            return _abort("victim rank %d exited %s before the rejoin "
                          "could be staged, expected the injected kill "
                          "(%d)" % (kill_rank, victim.returncode,
                                    KILL_EXIT_CODE))
        # relaunch only once the shrunk fleet is stepping again (its
        # "resume" journal event has landed), so the incident chain
        # reads worker-lost -> replan -> reshard -> join-request in
        # causal order rather than racing the shrink
        seen_resume = False
        while time.time() < deadline:
            if any(e.get("kind") == "resume"
                   for e in read_journal(telemetry_dir)):
                seen_resume = True
                break
            time.sleep(0.2)
        if not seen_resume:
            return _abort("survivors never resumed at world %d; cannot "
                          "stage the rejoin" % (world - 1))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # as the first lives: never the chip
        env["PADDLE_TPU_TELEMETRY_DIR"] = telemetry_dir
        env["PADDLE_TPU_TRACEPARENT"] = drill_tp
        env.setdefault("PADDLE_TPU_TELEMETRY_FLUSH", "1")
        # the second life joins clean — it must NOT re-inherit the kill
        env.pop("PADDLE_TPU_FAULT_SPEC", None)
        env.pop("PADDLE_TPU_FAULT_STATE_FILE", None)
        env.pop("PADDLE_TPU_NAN_GUARD", None)
        cmd = [sys.executable, "-m", "paddle_tpu.tools.chaos",
               "--elastic-worker", "--join", "--rank", str(kill_rank),
               "--world", str(world), "--steps", str(args.steps),
               "--ckpt-dir", workdir,
               "--stale-timeout", str(args.stale_timeout),
               "--worker-timeout", str(args.worker_timeout)]
        if args.step_delay:
            cmd += ["--step-delay", str(args.step_delay)]
        print("chaos[elastic]: victim died with %d; relaunching rank %d "
              "as a joiner" % (KILL_EXIT_CODE, kill_rank), flush=True)
        logf = open(os.path.join(
            workdir, "worker-r%d-rejoin.log" % kill_rank), "w+")
        logs.append(logf)
        procs.append(sp.Popen(cmd, env=env, stdout=logf,
                              stderr=sp.STDOUT))

    while any(p.poll() is None for p in procs) \
            and time.time() < deadline:
        time.sleep(0.2)
    hung = [r for r, p in enumerate(procs) if p.poll() is None]
    for p in procs:
        if p.poll() is None:
            p.kill()
            p.wait()
    outputs = []
    for logf in logs:
        logf.seek(0)
        outputs.append(logf.read())
        logf.close()
    rcs = [p.returncode for p in procs]
    print("chaos[elastic]: exit codes %s%s"
          % (rcs, " (killed hung: %s)" % hung if hung else ""),
          flush=True)
    if hung:
        print("chaos[elastic]: FAIL — worker(s) %s hung past %.0fs; "
              "rank 0 tail:\n%s" % (hung, args.worker_timeout,
                                    outputs[0][-2000:]), flush=True)
        return 2
    if rcs[kill_rank] != KILL_EXIT_CODE:
        print("chaos[elastic]: FAIL — victim rank %d exited %s, "
              "expected the injected kill (%d)"
              % (kill_rank, rcs[kill_rank], KILL_EXIT_CODE), flush=True)
        return 2
    survivors = [r for r in range(world) if r != kill_rank]
    bad = [r for r in survivors if rcs[r] != 0]
    if bad:
        print("chaos[elastic]: FAIL — survivor(s) %s exited nonzero; "
              "rank %d tail:\n%s"
              % (bad, bad[0], outputs[bad[0]][-3000:]), flush=True)
        return 2

    if args.rejoin:
        return _verify_rejoin(args, world, kill_rank, rcs, outputs,
                              telemetry_dir, drill_ctx)

    shrunk = world - 1
    parsed = {r: _parse_elastic_output(outputs[r]) for r in survivors}
    for r in survivors:
        steps_seen, final = parsed[r]
        missing = [k for k in range(args.steps) if k not in steps_seen]
        if missing or final is None:
            print("chaos[elastic]: FAIL — rank %d missed steps %s "
                  "(in-process resume must cover every step)"
                  % (r, missing), flush=True)
            return 2
        post = [k for k, (_i, w, _e, _l) in steps_seen.items()
                if w == shrunk]
        if not post or min(post) > args.kill_step:
            print("chaos[elastic]: FAIL — rank %d never re-ran step "
                  "%d at world %d (post-recovery steps: %s)"
                  % (r, args.kill_step, shrunk, sorted(post)),
                  flush=True)
            return 2
    digests = {parsed[r][1]["params_sha"] for r in survivors}
    if len(digests) != 1:
        print("chaos[elastic]: FAIL — survivors ended on different "
              "params: %s" % sorted(digests), flush=True)
        return 1
    print("chaos[elastic]: survivors recovered in-process at world=%d "
          "(one log per rank — no restarts) and agree on params %s"
          % (shrunk, next(iter(digests))[:16]), flush=True)

    # the oracle is bookkeeping: keep it out of the workers' telemetry
    from paddle_tpu.observability import metrics as _metrics

    _metrics.set_telemetry_enabled(False)
    try:
        oracle = _elastic_oracle(args.steps, shrunk)
    finally:
        _metrics.set_telemetry_enabled(None)
    worst = 0.0
    for r in survivors:
        steps_seen, _final = parsed[r]
        for k, (index, w, _epoch, lv) in sorted(steps_seen.items()):
            if w != shrunk:
                continue  # pre-kill steps ran at the full world
            want = oracle[k][index]
            rel = abs(lv - want) / max(abs(want), 1e-6)
            worst = max(worst, rel)
            if rel > args.tolerance:
                print("chaos[elastic]: FAIL — rank %d step %d loss "
                      "%.8f vs shrunk-world oracle %.8f (rel %.2e > "
                      "%.2e)" % (r, k, lv, want, rel, args.tolerance),
                      flush=True)
                return 1
    print("chaos[elastic]: post-recovery loss curve tracks the "
          "world-%d oracle (worst rel err %.2e <= %.2e)"
          % (shrunk, worst, args.tolerance), flush=True)

    from paddle_tpu.observability.journal import read_journal

    kinds = {e.get("kind") for e in read_journal(telemetry_dir)}
    chain = ["worker-lost", "replan", "reshard", "checkpoint-loaded",
             "resume"]
    gone = [k for k in chain if k not in kinds]
    if gone:
        print("chaos[elastic]: FAIL — journal is missing incident "
              "events %s (have %s)" % (gone, sorted(kinds)), flush=True)
        return 1
    print("chaos[elastic]: journal shows the full incident chain "
          "%s — view it with: python -m paddle_tpu.tools.monitor "
          "--once %s" % (" -> ".join(chain), telemetry_dir),
          flush=True)

    # every rank's spans — victim included — must have joined the ONE
    # drill trace, with the recovery phases visible inside it
    spans = [r for r in _tracing.read_traces(telemetry_dir)
             if r.get("trace") == drill_ctx.trace_id]
    span_ranks = {r.get("rank") for r in spans}
    span_names = {r.get("name") for r in spans}
    want_names = {"elastic.worker", "elastic.recover", "elastic.replan",
                  "elastic.restore"}
    missing_ranks = set(range(world)) - span_ranks
    missing_names = want_names - span_names
    if missing_ranks or missing_names:
        print("chaos[elastic]: FAIL — drill trace %s is missing "
              "rank(s) %s / span(s) %s (have ranks %s, %d spans)"
              % (drill_ctx.trace_id, sorted(missing_ranks),
                 sorted(missing_names), sorted(span_ranks), len(spans)),
              flush=True)
        return 1
    print("chaos[elastic]: ONE trace %s spans all %d ranks through "
          "recovery (%d spans) — reconstruct it with: python -m "
          "paddle_tpu.tools.trace --elastic %s"
          % (drill_ctx.trace_id, world, len(spans), telemetry_dir),
          flush=True)
    print("chaos[elastic]: PASS", flush=True)
    return 0


def _verify_rejoin(args, world, kill_rank, rcs, outputs, telemetry_dir,
                   drill_ctx):
    """Rejoin half of the verdict: the victim's second life joined, the
    fleet grew back to the full world, every run's losses track the
    per-world oracles, and the journal reads the whole incident —
    shrink, join, warm-up, grow — as ONE causally ordered trace."""
    from paddle_tpu.observability import metrics as _metrics
    from paddle_tpu.observability import tracing as _tracing
    from paddle_tpu.observability.journal import read_journal

    survivors = [r for r in range(world) if r != kill_rank]
    if rcs[-1] != 0:
        print("chaos[elastic]: FAIL — the victim's second life exited "
              "%s (a rejoined worker must exit 0); tail:\n%s"
              % (rcs[-1], outputs[-1][-3000:]), flush=True)
        return 2

    parsed = {r: _parse_elastic_output(outputs[r]) for r in survivors}
    jsteps, jfinal = _parse_elastic_output(outputs[-1])
    for r in survivors:
        steps_seen, final = parsed[r]
        missing = [k for k in range(args.steps) if k not in steps_seen]
        if missing or final is None:
            print("chaos[elastic]: FAIL — rank %d missed steps %s "
                  "(in-process resume must cover every step)"
                  % (r, missing), flush=True)
            return 2
        if int(final["world"]) != world:
            print("chaos[elastic]: FAIL — rank %d finished at world=%s; "
                  "the fleet never grew back to %d"
                  % (r, final["world"], world), flush=True)
            return 2
    if jfinal is None or int(jfinal["world"]) != world:
        print("chaos[elastic]: FAIL — the joiner finished at world=%s "
              "(want %d); tail:\n%s"
              % (jfinal and jfinal.get("world"), world,
                 outputs[-1][-3000:]), flush=True)
        return 2
    if not jsteps:
        print("chaos[elastic]: FAIL — the joiner was admitted but ran "
              "no steps", flush=True)
        return 2
    off_world = sorted(k for k, (_i, w, _e, _l) in jsteps.items()
                       if w != world)
    if off_world:
        print("chaos[elastic]: FAIL — the joiner stepped outside the "
              "grown world at steps %s (must only run at world=%d)"
              % (off_world, world), flush=True)
        return 2
    join_step = min(jsteps)
    if join_step <= args.kill_step:
        print("chaos[elastic]: FAIL — the joiner's first step %d is "
              "not after the kill at step %d" % (join_step,
                                                 args.kill_step),
              flush=True)
        return 2
    digests = {parsed[r][1]["params_sha"] for r in survivors}
    digests.add(jfinal["params_sha"])
    if len(digests) != 1:
        print("chaos[elastic]: FAIL — survivors and joiner ended on "
              "different params: %s" % sorted(digests), flush=True)
        return 1
    print("chaos[elastic]: fleet grew back to world=%d (joiner entered "
          "at step %d) and all %d workers agree on params %s"
          % (world, join_step, world, next(iter(digests))[:16]),
          flush=True)

    # two oracles: world-N before the kill and after the grow,
    # world-(N-1) in between — every printed step names its world and
    # shard index, so each loss is compared against the right one
    _metrics.set_telemetry_enabled(False)
    try:
        oracles = {world: _elastic_oracle(args.steps, world),
                   world - 1: _elastic_oracle(args.steps, world - 1)}
    finally:
        _metrics.set_telemetry_enabled(None)
    runs = [("rank %d" % r, parsed[r][0]) for r in survivors]
    runs.append(("rank %d (rejoined)" % kill_rank, jsteps))
    worst = 0.0
    for label, steps_seen in runs:
        for k, (index, w, _epoch, lv) in sorted(steps_seen.items()):
            want = oracles[w][k][index]
            rel = abs(lv - want) / max(abs(want), 1e-6)
            worst = max(worst, rel)
            if rel > args.tolerance:
                print("chaos[elastic]: FAIL — %s step %d loss %.8f vs "
                      "world-%d oracle %.8f (rel %.2e > %.2e)"
                      % (label, k, lv, w, want, rel, args.tolerance),
                      flush=True)
                return 1
    print("chaos[elastic]: loss curve tracks the world-%d/world-%d "
          "oracles across shrink and grow (worst rel err %.2e <= %.2e)"
          % (world, world - 1, worst, args.tolerance), flush=True)

    # the whole incident must read causally in ONE trace: walk the
    # required kinds, each picked event at-or-after the previous one
    events = sorted(read_journal(telemetry_dir),
                    key=lambda e: e.get("ts", 0.0))
    chain = ["worker-lost", "replan", "reshard", "join-request",
             "admitted", "warmup", "replan", "reshard", "resume"]
    t = float("-inf")
    for kind in chain:
        pick = next(
            (e for e in events
             if e.get("kind") == kind and e.get("ts", 0.0) >= t
             and e.get("trace") == drill_ctx.trace_id), None)
        if pick is None:
            have = sorted({e.get("kind") for e in events})
            print("chaos[elastic]: FAIL — journal has no '%s' event "
                  "after the previous link in trace %s (chain %s, "
                  "kinds present: %s)"
                  % (kind, drill_ctx.trace_id, " -> ".join(chain),
                     have), flush=True)
            return 1
        t = pick.get("ts", t)
    print("chaos[elastic]: journal reads %s in causal order inside "
          "one trace — view it with: python -m paddle_tpu.tools."
          "monitor --once %s" % (" -> ".join(chain), telemetry_dir),
          flush=True)

    spans = [s for s in _tracing.read_traces(telemetry_dir)
             if s.get("trace") == drill_ctx.trace_id]
    span_ranks = {s.get("rank") for s in spans}
    span_names = {s.get("name") for s in spans}
    want_names = {"elastic.worker", "elastic.recover", "elastic.replan",
                  "elastic.restore", "elastic.join", "elastic.warmup",
                  "elastic.grow"}
    missing_ranks = set(range(world)) - span_ranks
    missing_names = want_names - span_names
    if missing_ranks or missing_names:
        print("chaos[elastic]: FAIL — drill trace %s is missing "
              "rank(s) %s / span(s) %s (have ranks %s, %d spans)"
              % (drill_ctx.trace_id, sorted(missing_ranks),
                 sorted(missing_names), sorted(span_ranks), len(spans)),
              flush=True)
        return 1
    print("chaos[elastic]: ONE trace %s spans all %d ranks through "
          "shrink, rejoin and grow (%d spans)"
          % (drill_ctx.trace_id, world, len(spans)), flush=True)

    rejoin_ms = [e.get("rejoin_ms") for e in events
                 if e.get("kind") == "resume"
                 and e.get("rejoin_ms") is not None]
    if rejoin_ms:
        print("chaos[elastic]: elastic_rejoin_ms=%.0f (join request -> "
              "first grown step)" % rejoin_ms[-1], flush=True)
    print("chaos[elastic]: PASS", flush=True)
    return 0


def _parse_worker_output(text, losses, skipped):
    final = None
    resumed = []
    for line in text.splitlines():
        if line.startswith("CHAOS_STEP "):
            parts = line.split()
            k = int(parts[1])
            losses[k] = float(parts[2].split("=")[1])
            if int(parts[3].split("=")[1]):
                skipped.add(k)
            else:
                # a later incarnation re-ran this step cleanly (e.g. the
                # skip happened just before a crash and the resumed
                # worker applied it): the newest verdict wins
                skipped.discard(k)
        elif line.startswith("CHAOS_FINAL "):
            final = line.split()[1].split("=")[1]
        elif line.startswith("CHAOS_RESUME "):
            resumed.append(int(line.split()[1].split("=")[1]))
    return final, resumed


def _run_driver(args):
    from paddle_tpu.resilience import retry as _retry

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="paddle_tpu_chaos_")
    from paddle_tpu.resilience import checkpoint as _ckpt

    existing = _ckpt.list_checkpoints(ckpt_dir)
    if existing and existing[0][0] >= args.steps - 1:
        print("chaos: ERROR — --ckpt-dir already holds a completed run "
              "(newest version: step %d); the worker would resume past "
              "every step.  Use a fresh --ckpt-dir." % existing[0][0],
              flush=True)
        return 2
    losses, skipped, final_sha = {}, set(), None
    all_resumes = []
    backoff = _retry.RetryPolicy(max_attempts=args.max_restarts + 1,
                                 base_delay=0.2, max_delay=2.0, seed=7)
    delays = backoff.delays()
    # the drill doubles as the observability acceptance scenario: every
    # incarnation journals into one shared dir, so the monitor CLI can
    # replay the fault -> guard-skip -> restore story afterwards
    from paddle_tpu.observability.metrics import telemetry_enabled

    telemetry_dir = args.telemetry_dir
    if telemetry_dir is None and telemetry_enabled():
        telemetry_dir = os.path.join(ckpt_dir, "telemetry")
    print("chaos: spec=%r steps=%d ckpt=%s telemetry=%s"
          % (args.spec, args.steps, ckpt_dir, telemetry_dir or "off"),
          flush=True)

    from paddle_tpu.observability import tracing as _tracing

    # one trace across every incarnation of the worker
    drill_tp = _tracing.format_traceparent(_tracing.new_trace_context())

    for incarnation in range(args.max_restarts + 1):
        env = dict(os.environ)
        env.update({
            "PADDLE_TPU_FAULT_SPEC": args.spec,
            # firing budgets span restarts: a worker_kill is ONE
            # preemption, not one per incarnation
            "PADDLE_TPU_FAULT_STATE_FILE":
                os.path.join(ckpt_dir, "fault_state.json"),
            "PADDLE_TPU_NAN_GUARD": "1",
            "PADDLE_TPU_TRACEPARENT": drill_tp,
            # the worker trains on the CPU: a parent that holds the
            # chip cannot share it with a child
            "JAX_PLATFORMS": "cpu",
        })
        env.setdefault("PADDLE_TPU_TELEMETRY_FLUSH", "1")
        if telemetry_dir:
            env["PADDLE_TPU_TELEMETRY_DIR"] = telemetry_dir
        cmd = [sys.executable, "-m", "paddle_tpu.tools.chaos", "--worker",
               "--steps", str(args.steps), "--ckpt-dir", ckpt_dir]
        with tempfile.NamedTemporaryFile("w+", suffix=".log",
                                         delete=False) as logf:
            t0 = time.time()
            proc = subprocess.Popen(cmd, env=env, stdout=logf,
                                    stderr=subprocess.STDOUT)
            try:
                rc = proc.wait(timeout=args.worker_timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                rc = "timeout"
            logf.seek(0)
            out = logf.read()
        final_sha, resumes = _parse_worker_output(out, losses, skipped)
        all_resumes.extend(resumes)
        print("chaos: incarnation %d rc=%s (%.1fs) steps_done=%d"
              % (incarnation, rc, time.time() - t0, len(losses)),
              flush=True)
        if rc == 0 and final_sha is not None:
            break
        if incarnation == args.max_restarts:
            print("chaos: FAIL — worker never completed within %d "
                  "restarts; last output:\n%s"
                  % (args.max_restarts, out[-2000:]), flush=True)
            return 2
        try:
            delay = next(delays)
        except StopIteration:
            delay = 1.0
        print("chaos: restarting worker (auto-resume) in %.2fs" % delay,
              flush=True)
        time.sleep(delay)

    missing = [k for k in range(args.steps) if k not in losses]
    if missing:
        print("chaos: FAIL — steps %s never ran" % missing, flush=True)
        return 2
    print("chaos: worker recovered; skipped steps=%s resumes=%s"
          % (sorted(skipped), all_resumes), flush=True)

    # the oracle replay is bookkeeping, not training: keep its steps and
    # checkpoints out of the telemetry the workers just wrote
    from paddle_tpu.observability import metrics as _metrics

    _metrics.set_telemetry_enabled(False)
    try:
        oracle = _oracle_digest(args.steps, skipped, args.spec)
    finally:
        _metrics.set_telemetry_enabled(None)
    if oracle != final_sha:
        print("chaos: FAIL — final params %s != fault-free oracle %s "
              "(recovery diverged)" % (final_sha[:16], oracle[:16]),
              flush=True)
        return 1
    print("chaos: PASS — final params match the fault-free trajectory "
          "(sha %s)" % final_sha[:16], flush=True)
    return 0


def _run_quant_driver(args):
    """ISSUE-15 acceptance drill: quantized vs dense collective twins.

    Both twins train the same deterministic model from the same seed on
    the same batches, each step splitting the batch across a simulated
    2-rank data-parallel ring.  The control sums the per-rank gradients
    in full precision; the quant twin runs them through the identical
    quantize → dequant-sum → requant → dequant pipeline the
    ``c_allreduce_quant`` op executes on the wire (same primitives, same
    block size, same fixed reduction order), so the injected error IS
    the collective's error — not a stand-in.  Runs on one CPU device;
    no mesh is needed because a 2-rank quantized ring's arithmetic is
    rank-count-independent pointwise math once the shards are in hand.
    """
    _force_cpu()
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from paddle_tpu.observability.drift import monitor, reset_drift
    from paddle_tpu.quant import (block_dequantize, block_quantize,
                                  predicted_rms_error, quant_block)

    steps = max(args.steps, 6)
    lr = 0.05
    print("chaos: quant A/B drill — %d steps, block=%d, tolerance=%g"
          % (steps, quant_block(), args.tolerance), flush=True)

    def init_params():
        k = jax.random.PRNGKey(_MODEL_SEED)
        k1, k2 = jax.random.split(k)
        return {
            "w1": jax.random.normal(k1, (_FEATS, _HIDDEN)) * 0.5,
            "b1": jnp.zeros((_HIDDEN,)),
            "w2": jax.random.normal(k2, (_HIDDEN, 1)) * 0.5,
            "b2": jnp.zeros((1,)),
        }

    def loss_fn(params, xb, yb):
        h = jnp.maximum(xb @ params["w1"] + params["b1"], 0.0)
        p = h @ params["w2"] + params["b2"]
        return jnp.mean((p - yb) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    def quant_reduce(flats):
        """Mirror quantized_allreduce on already-materialized shards:
        each rank's contribution crosses the wire as int8 + scales both
        directions (reduce-scatter then allgather)."""
        numel = int(flats[0].size)
        parts, preds = [], []
        for f in flats:
            q, s = block_quantize(f)
            parts.append(block_dequantize(q, s, size=numel))
            preds.append(float(predicted_rms_error(s)))
        acc = parts[0]
        for p in parts[1:]:
            acc = acc + p
        q_r, s_r = block_quantize(acc)
        out = block_dequantize(q_r, s_r, size=numel)
        preds.append(float(predicted_rms_error(s_r)))
        predicted = float(np.sqrt(sum(p * p for p in preds)))
        return out, predicted

    reset_drift()
    mon = monitor()
    params_c = init_params()
    params_q = jax.tree_util.tree_map(lambda a: a, params_c)
    losses_c, losses_q = [], []
    worst_rel, worst_err_ratio = 0.0, 0.0
    for k, (xb, yb) in enumerate(_batches(steps)):
        half = _BATCH // 2
        shards = [(xb[:half], yb[:half]), (xb[half:], yb[half:])]

        # control twin: dense mean-of-shards reduction
        lv_c = grad_fn(params_c, xb, yb)[0]
        gflats_c = []
        unravel = None
        for xs, ys in shards:
            _, g = grad_fn(params_c, xs, ys)
            flat, unravel = ravel_pytree(g)
            gflats_c.append(flat * 0.5)
        dense_c = gflats_c[0] + gflats_c[1]
        params_c = unravel(ravel_pytree(params_c)[0] - lr * dense_c)

        # quant twin: same shards, int8 wire reduction; the dense sum of
        # ITS OWN gradients is the per-step error reference
        lv_q = grad_fn(params_q, xb, yb)[0]
        gflats_q = []
        for xs, ys in shards:
            _, g = grad_fn(params_q, xs, ys)
            flat, _ = ravel_pytree(g)
            gflats_q.append(flat * 0.5)
        dense_q = gflats_q[0] + gflats_q[1]
        reduced, predicted = quant_reduce(gflats_q)
        measured = float(jnp.sqrt(jnp.mean((reduced - dense_q) ** 2)))
        mon.observe_quant_error(measured, predicted=predicted,
                                bucket="grads")
        if predicted > 0:
            worst_err_ratio = max(worst_err_ratio, measured / predicted)
        params_q = unravel(ravel_pytree(params_q)[0] - lr * reduced)

        lc, lq = float(lv_c), float(lv_q)
        losses_c.append(lc)
        losses_q.append(lq)
        rel = abs(lq - lc) / max(abs(lc), 1e-8)
        worst_rel = max(worst_rel, rel)
        print("CHAOS_QUANT_STEP %d loss_dense=%.8f loss_quant=%.8f "
              "rel=%.2e quant_rms=%.3e model_rms=%.3e"
              % (k, lc, lq, rel, measured, predicted), flush=True)

    converged_c = losses_c[-1] < losses_c[0]
    converged_q = losses_q[-1] < losses_q[0]
    # 3x headroom over the RMS model: per-step error is a random draw,
    # the model is its expectation
    model_ok = worst_err_ratio <= 3.0
    print("chaos: quant drill worst_loss_rel=%.2e worst_error_vs_model="
          "%.2fx converged dense=%s quant=%s"
          % (worst_rel, worst_err_ratio, converged_c, converged_q),
          flush=True)
    if worst_rel > args.tolerance:
        print("chaos: FAIL — quant twin loss diverged %.2e > "
              "tolerance %g" % (worst_rel, args.tolerance), flush=True)
        return 1
    if not (converged_c and converged_q):
        print("chaos: FAIL — a twin failed to converge "
              "(dense %s, quant %s)" % (converged_c, converged_q),
              flush=True)
        return 1
    if not model_ok:
        print("chaos: FAIL — measured quant error %.2fx the documented "
              "model (alert 'quant_error_ratio>2' would page)"
              % worst_err_ratio, flush=True)
        return 1
    print("chaos: PASS — quantized twin matched the dense loss curve "
          "within %g and the error stayed inside the model"
          % args.tolerance, flush=True)
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m paddle_tpu.tools.chaos",
        description="Fault-injection chaos run: train, inject, recover, "
                    "verify against the fault-free trajectory.")
    parser.add_argument("--spec", default=os.environ.get(
        "PADDLE_TPU_FAULT_SPEC",
        "nan_grad@step=3;ckpt_write_fail@step=5;worker_kill@step=7"),
        help="fault spec (see resilience/faults.py grammar)")
    parser.add_argument("--steps", type=int, default=None,
                        help="training steps (default 9; 24 for "
                             "--elastic --rejoin so the joiner has "
                             "live steps left to enter)")
    parser.add_argument("--ckpt-dir", default=None)
    parser.add_argument("--telemetry-dir", default=None,
                        help="journal/metrics dir for the workers "
                             "(default: <ckpt-dir>/telemetry when "
                             "telemetry is on); tail it with "
                             "python -m paddle_tpu.tools.monitor")
    parser.add_argument("--max-restarts", type=int, default=3)
    parser.add_argument("--worker-timeout", type=float, default=300.0,
                        help="seconds per worker incarnation (bounds "
                             "injected hangs)")
    parser.add_argument("--elastic", action="store_true",
                        help="run the elastic drill instead: kill one "
                             "of --elastic-world workers mid-run and "
                             "demand an in-process re-plan/reshard/"
                             "resume at the shrunk world size")
    parser.add_argument("--quant", action="store_true",
                        help="run the quantized-collective A/B drill "
                             "instead: same-seed twins (dense vs int8 "
                             "block-quantized gradient reduction) must "
                             "match loss curves within --tolerance")
    parser.add_argument("--rejoin", action="store_true",
                        help="with --elastic: after the shrink "
                             "recovery, relaunch the victim as a "
                             "joiner and demand the fleet grows back "
                             "to the full world (matching digests, "
                             "causally ordered journal, one trace)")
    parser.add_argument("--step-delay", type=float, default=None,
                        help="seconds each worker sleeps per step "
                             "(default 0; 0.4 for --rejoin so the "
                             "joiner warms up behind a live fleet)")
    parser.add_argument("--elastic-world", type=int, default=3,
                        help="elastic cluster size before the kill")
    parser.add_argument("--kill-step", type=int, default=3,
                        help="step at which the victim is killed")
    parser.add_argument("--kill-rank", type=int, default=None,
                        help="victim rank (default: highest rank, so "
                             "the leader path stays exercised; pick 0 "
                             "to drill a leader loss)")
    parser.add_argument("--tolerance", type=float, default=0.02,
                        help="max relative loss error vs the "
                             "shrunk-world oracle")
    parser.add_argument("--stale-timeout", type=float, default=2.0,
                        help="seconds without a heartbeat before a "
                             "peer is declared lost")
    parser.add_argument("--worker", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--elastic-worker", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--join", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--rank", type=int, default=0,
                        help=argparse.SUPPRESS)
    parser.add_argument("--world", type=int, default=1,
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    rejoin_drill = args.elastic and args.rejoin
    if args.steps is None:
        args.steps = 24 if rejoin_drill else 9
    if args.step_delay is None:
        args.step_delay = 0.4 if rejoin_drill else 0.0
    if args.worker:
        return _run_worker(args)
    if args.elastic_worker:
        return _run_elastic_worker(args)
    if args.quant:
        return _run_quant_driver(args)
    if args.elastic:
        return _run_elastic_driver(args)
    return _run_driver(args)


if __name__ == "__main__":
    import numpy as np  # noqa: F401  (worker fast-fail if numpy absent)

    sys.exit(main())
