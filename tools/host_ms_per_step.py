"""The host's time inside ``Executor.run`` per step, from an untraced run.

One run of a benchmark cell from the checkout ROOT, as the driver runs it
(``chipbench/run.py --workload W --seed S --seconds 30 --trace 0``, in this
process), plus one line more before the result: the median, mean and
longest of the loop's ``dispatch_s`` over the measured window (what
``dispatch_ms.train`` reads in a traced run).  Nothing of the run changes:
the traffic driver's ``measure`` is wrapped where ``run.py`` loads it.

    usage: host_ms_per_step.py ROOT TAG WORKLOAD SEED

PERF.md's cost table (PR 26) is this, parent (P: ``git archive HEAD`` under
``.scratch/parent``) against change (C), in one ``chiprun`` call::

    export PADDLE_TPU_TRACING=0 PADDLE_TPU_TELEMETRY=0
    for cell in bert_base_train_seq128_bs128 resnet50_train_bs128; do
      for pair in "P C 2600000101" "C P 2600000102" "P C 2600000103"; do
        set -- $pair
        for side in $1 $2; do
          [ $side = P ] && root=.scratch/parent || root=.
          python3 tools/host_ms_per_step.py $root $side $cell $3
        done; done; done
"""
import json
import os
import runpy
import statistics
import sys

root, tag, workload, seed = sys.argv[1:5]
root = os.path.abspath(root)
os.chdir(root)
sys.path.insert(0, root)
from chipbench import manifest as mf  # noqa: E402

load = mf.load_by_name


def load_and_wrap(kind, name, *args, **kwargs):
    module = load(kind, name, *args, **kwargs)
    if kind == "traffic" and hasattr(module, "measure"):
        measure = module.measure

        def measured(*a, **k):
            out = measure(*a, **k)
            d = out["dispatch_s"][out["first"]:out["first"] + out["steps"]]
            print(json.dumps({
                "tag": tag, "workload": workload, "seed": int(seed),
                "host_ms_median": 1e3 * statistics.median(d),
                "host_ms_mean": 1e3 * statistics.fmean(d),
                "host_ms_max": 1e3 * max(d), "steps": len(d),
                "step_ms_median": 1e3 * statistics.median(out["intervals_s"]),
                "step_ms_max": 1e3 * max(out["intervals_s"])}), flush=True)
            return out

        module.measure = measured
    return module


mf.load_by_name = load_and_wrap
sys.argv = ["chipbench/run.py", "--workload", workload, "--seed", seed,
            "--seconds", "30", "--trace", "0"]
runpy.run_path(os.path.join(root, "chipbench", "run.py"), run_name="__main__")
