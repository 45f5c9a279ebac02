"""Is the step the same step?  One run of a benchmark cell from the checkout
ROOT, as the driver runs it (``chipbench/run.py --workload W --seed S
--seconds N --trace T``, in this process), plus one line more before the
result, for holding a change against its parent at the same seed:

* ``first_losses``: the first 20 losses, as the bytes of their float32;
* ``kernels``: the Mosaic kernels of the compiled step, by name;
* ``hlo_sha256``: a digest of the compiled step's text without its
  ``metadata={...}`` and its tables of files, functions and stack frames
  (source paths and lines move with every checkout and every edit above
  the traced code; the program they annotate does not);
* ``grad_residual_sites``: the program's ``grad_residual_sites_total`` by
  ``op_type/path``, and ``grouped_dot_sites``: its
  ``grouped_dot_sites_total`` by ``mode/path`` (each empty where the
  program has no such counter).

Nothing of the run changes: the traffic driver's ``measure`` and
``check_step`` are wrapped where ``run.py`` loads it.

    usage: step_identity.py ROOT TAG WORKLOAD SEED [SECONDS [TRACE]]

Parent (P: ``git archive HEAD`` under the gitignored ``.scratch/parent``)
against change (C) in one ``chiprun`` call, as PERF.md's PR 27 table::

    for cell in bert_base_train_seq512_bs32 bert_base_train_seq128_bs128; do
      for pair in "P C 2700000101" "C P 2700000102" "P C 2700000103"; do
        set -- $pair
        for side in $1 $2; do
          [ $side = P ] && root=.scratch/parent || root=.
          python3 tools/step_identity.py $root $side $cell $3
        done; done; done
"""
import hashlib
import json
import os
import re
import runpy
import struct
import sys

root, tag, workload, seed = sys.argv[1:5]
seconds = sys.argv[5] if len(sys.argv) > 5 else "30"
trace = sys.argv[6] if len(sys.argv) > 6 else "0"
root = os.path.abspath(root)
os.chdir(root)
sys.path.insert(0, root)
from chipbench import manifest as mf  # noqa: E402

load = mf.load_by_name
_SOURCES = re.compile(
    r", metadata=\{[^}]*\}|^(?:FileNames|FunctionNames|FileLocations|"
    r"StackFrames)\n(?:\d+ .*\n)*", re.M)
seen = {"tag": tag, "workload": workload, "seed": int(seed)}


def sites(counter, kind):
    """``{"<kind>/<path>": count}`` of the program's counter ``counter``."""
    from paddle_tpu.observability import metrics

    return {"%s/%s" % (dict(m.labels).get(kind),
                       dict(m.labels).get("path")): m.value
            for m in metrics.registry().collect() if m.name == counter}


def load_and_wrap(kind, name, *args, **kwargs):
    module = load(kind, name, *args, **kwargs)
    if kind == "traffic" and hasattr(module, "measure"):
        measure, check_step = module.measure, module.check_step

        def measured(*a, **k):
            out = measure(*a, **k)
            seen["first_losses"] = [struct.pack("<f", x).hex()
                                    for x in out["losses"][:20]]
            seen["step_ms_median"] = 1e3 * sorted(out["intervals_s"])[
                len(out["intervals_s"]) // 2]
            return out

        def checked(compiled, *a, **k):
            text = compiled.as_text()
            seen["hlo_sha256"] = hashlib.sha256(_SOURCES.sub(
                "", text).encode()).hexdigest()
            res = check_step(compiled, *a, **k)
            seen["kernels"] = res[1]
            seen["grad_residual_sites"] = sites("grad_residual_sites_total",
                                                "op_type")
            seen["grouped_dot_sites"] = sites("grouped_dot_sites_total",
                                              "mode")
            print(json.dumps(seen), flush=True)
            return res

        module.measure, module.check_step = measured, checked
    return module


mf.load_by_name = load_and_wrap
sys.argv = ["chipbench/run.py", "--workload", workload, "--seed", seed,
            "--seconds", seconds, "--trace", trace]
runpy.run_path(os.path.join(root, "chipbench", "run.py"), run_name="__main__")
