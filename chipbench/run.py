"""Runs one cell of ``BENCHMARK.json`` once, on the chips it asks for.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, last,
``compared``: each number that decided ``correct`` beside its limit, which
are also the last lines of standard error.  Earlier lines are
for people: the set-up's parts, the median step, tokens per second, the
compiled step's memory.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result: no number ever comes
from a CPU.

This file holds no table of cells, configurations or metrics, and knows
nothing of training or serving.  It finds the cell's data by name
(``manifest.load_cell``) and the traffic driver by the name that data gives
(``traffic/<driver>.py``).  The driver's ``drive(cell, seed, seconds,
trace_dir, t_start)`` runs the cell and returns its state: ``metrics`` (the
end-to-end values but ``setup_s``), ``t_window`` (where set-up ends),
``attempted``, ``failed``, ``clocks`` and a ``report`` for the earlier
lines.  Its ``verify(state, cell, devices)`` returns the problems that make
the run not ``correct``, and leaves ``kernels`` and ``op_names`` in the
state for the trace reduction and ``compared`` for the result's line.
This file adds ``setup_s`` and the device's memory, picks the metrics the
manifest names for the cell, and finds each per-layer metric's reader
under ``layer_metrics/`` by the metric's name.
"""

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import manifest as mf  # noqa: E402
from chipbench import xplane  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


def say(**fields):
    print(json.dumps(fields), flush=True)


def fail(message):
    """Ends the run non-zero with nothing on standard output."""
    sys.stderr.write("chipbench: %s\n" % message)
    sys.exit(1)


def peaks_for(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError("no peaks known for device_kind %r (peaks.json has: "
                       "%s)" % (device_kind, ", ".join(sorted(table))))
    return table[device_kind]


def require_chips(chips):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail("JAX reports platform %r; the benchmark runs on the TPU only"
             % devices[0].platform)
    if len(devices) != chips:
        fail("the cell asks for %d chips and JAX reports %d (the program "
             "uses every device it sees)" % (chips, len(devices)))
    return devices


def peak_bytes(stats):
    """Peak use of one device's memory, from the runtime's counters alone
    (``Device.memory_stats()``): ``peak_bytes_in_use``, the buffers it has
    handed out (weights, optimizer state, feeds, fetches), plus
    ``peak_bytes_reserved``, what it holds back for the temporaries of the
    loaded executables.  This TPU runtime keeps the two apart (the seq128
    step ran under 1.47 GB in use and 6.06 GB reserved: PERF.md, PR 24);
    a runtime without the second counter reports the first alone."""
    return stats.get("peak_bytes_in_use", 0) \
        + stats.get("peak_bytes_reserved", 0)


def traced_result(manifest, cell, trace, ctx):
    """The ``--trace 1`` half of the result line: the cell's per-layer
    metrics, each from its own reader, and the breakdown."""
    metrics = {}
    for name, m in mf.metrics_of(manifest, "per_layer", cell).items():
        value = mf.load_by_name("layer_metrics", name).read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    # seconds per step: the categories, and each kernel under its name
    parts = dict(trace["category_s"], **{"kernel:" + k: v for k, v
                                         in trace["kernel_s"].items()})
    top = sorted(parts.items(), key=lambda kv: -kv[1])[:10]
    breakdown = {"device_ops": [list(kv) for kv in top],
                 "idle_gaps": [list(kv) for kv in trace["gaps"]]}
    return metrics, breakdown


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        fail("no paddle_tpu/ beside chipbench/: nothing to measure here")
    manifest = mf.load_manifest()
    try:
        cell = mf.load_cell(args.workload, manifest)
    except mf.ManifestError as e:
        fail(str(e))
    workload, config, traffic = (cell["workload"], cell["config"],
                                 cell["traffic"])
    devices = require_chips(workload["chips"])
    kind = devices[0].device_kind
    peaks = peaks_for(kind)

    from paddle_tpu.core import configure_compile_cache

    cache_dir = configure_compile_cache()
    driver = mf.load_by_name("traffic", traffic["driver"])
    # the program's PRNG takes 32 signed bits; the driver's seeds are larger
    seed = args.seed % (2 ** 31 - 1)

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    state = driver.drive(cell, seed, args.seconds,
                         trace_dir=TRACE_DIR if args.trace else None,
                         t_start=T_START)
    measured = dict(state["metrics"], setup_s=state["t_window"] - T_START)
    wanted = mf.metrics_of(manifest, "end_to_end", workload["name"])
    if set(wanted) - set(measured):
        fail("the traffic driver %r yields no %s" % (
            traffic["driver"], ", ".join(sorted(set(wanted) - set(measured)))))
    # the memory the traffic used, before the checks load programs of
    # their own
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(peak_bytes(s) for s in stats)}
    problems = driver.verify(state, cell, devices)

    say(workload=workload["name"], seed=args.seed, device=device,
        compile_cache_dir=cache_dir, setup_parts=state["clocks"], **measured)
    say(memory_stats=stats[0], problems=problems, **state["report"])

    result = {"correct": not problems, "attempted": state["attempted"],
              "failed": state["failed"], "device": device}
    if not args.trace:
        result["metrics"] = {n: {"value": measured[n], "unit": m["unit"]}
                             for n, m in wanted.items()}
    else:
        path = xplane.newest_xplane(TRACE_DIR)
        trace = xplane.reduce_trace(
            xplane.read(path, state["op_names"]), state["kernels"]) \
            if path else None
        if trace is None:
            fail("the traced window shows no device running the step")
        ctx = {"cell": cell, "state": state, "trace": trace,
               "measured": measured, "device": device, "peaks": peaks,
               "flops": mf.load_by_name("flops", config["flops"])}
        result["metrics"], result["breakdown"] = traced_result(
            manifest, workload["name"], trace, ctx)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        say(traced_steps=trace["steps"],
            category_ms_per_step={k: 1e3 * v for k, v in sorted(
                trace["category_s"].items(), key=lambda kv: -kv[1])},
            kernel_ms_per_step={k: 1e3 * v
                                for k, v in trace["kernel_s"].items()},
            kernel_calls_per_step=trace["kernel_calls"],
            program_op_ms_per_step={k: 1e3 * v for k, v in sorted(
                trace["tag_s"].items(), key=lambda kv: -kv[1])[:15]},
            collective_ms_per_step=1e3 * trace["collective_s"])
    # each number compared beside its limit: last in the line, and the
    # last lines of standard error
    result["compared"] = state.get("compared", {})
    for name, pair in result["compared"].items():
        sys.stderr.write("chipbench: compared %s %s\n"
                         % (name, json.dumps(pair)))
    if problems:
        sys.stderr.write("chipbench: not correct: %s\n" % "; ".join(problems))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
