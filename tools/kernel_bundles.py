"""How long is a flash kernel's inner loop, without a chip?  Compiles
forward and backward of ``flash_attention`` for a described v5e with the
TPU compiler's own dump on, and prints each kernel's final schedule by
region: its bundles (one issue slot each; the kernels ran at about 1.3
of them a nanosecond on the chip, PERF.md section 6, PR 33) and what they
hold.  A loop body (``LB``) that holds 16 bundles a ``vmatmul`` of one MXU
is bound by the matrix unit; ``vrot`` / ``vperm`` by the hundred are a
relayout that a transpose (``vxpose``) does in a fifth.

    usage: JAX_PLATFORMS=cpu python tools/kernel_bundles.py [kanana|bert]
           [ROOT]      (ROOT: another checkout, as .scratch/parent)

Nothing runs on a device: a count of bundles is no time.  The compile
runs in a child, because the dumper can abort after the schedules are
written (it looks for a report template this install lacks).
"""
import collections
import glob
import os
import re
import shutil
import subprocess
import sys
import tempfile


def compile_kernels(case, root):
    """Forward and backward of one site, compiled for a described v5e."""
    os.environ.pop("PADDLE_TPU_PALLAS", None)
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    pallas.device_platform = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    if case == "bert":      # seq512: a key bias, in-kernel dropout, one block
        b, h, t, d, dv, kw = 2, 12, 512, 64, 64, dict(
            bias=jnp.zeros((2, 512), jnp.float32), dropout_rate=0.1,
            dropout_seed=jnp.ones((1,), jnp.int32))
    else:                   # kanana: causal, 8 x 8 blocks, 192 / 128
        b, h, t, d, dv, kw = 1, 4, 4096, 192, 128, dict(causal=True)
    args = [jax.ShapeDtypeStruct((b, h, t, w), jnp.bfloat16, sharding=chip)
            for w in (d, d, dv)]
    jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, **kw).astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(*args).compile()


if sys.argv[1:2] == ["--compile"]:
    compile_kernels(*sys.argv[2:4])
    sys.exit(0)

case = sys.argv[1] if len(sys.argv) > 1 else "kanana"
root = os.path.abspath(sys.argv[2] if len(sys.argv) > 2
                       else os.path.join(os.path.dirname(__file__), ".."))
dump = tempfile.mkdtemp(prefix="llo_")
subprocess.run(
    [sys.executable, os.path.abspath(__file__), "--compile", case, root],
    env=dict(os.environ, TPU_LOG_DIR="disabled", LIBTPU_INIT_ARGS=(
        "--xla_jf_dump_to=%s --xla_jf_dump_llo_text=true" % dump)),
    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
BUNDLE = re.compile(
    r"\s*0x[0-9a-f]+\s+(LB|PF|LH|LE|PB|CT)?\s*:\s*(>*)\s*\{(.*)\}")
for path in sorted(glob.glob(os.path.join(dump, "*final_bundles.txt"))):
    name = os.path.basename(path).split("-", 1)[1]
    if "flash_attention" not in name or "schedule-analysis" in path:
        continue
    print(name.split(".")[0])
    regions, depth_was, label = collections.OrderedDict(), 0, "top"
    for line in open(path):
        m = BUNDLE.match(line)
        if not m:
            continue
        tag, depth, body = m.group(1), len(m.group(2)), m.group(3)
        if tag in ("LB", "PF") or depth != depth_was:
            label = "%s%d depth %d" % (tag or "r", len(regions), depth)
        depth_was = depth
        n_ops = regions.setdefault(label, [0, collections.Counter()])
        n_ops[0] += 1
        for ins in filter(None, map(str.strip, body.split(";;"))):
            op = re.search(r"=\s*([a-z0-9_.]+)", ins)
            n_ops[1][(op.group(1) if op else ins.split()[0])
                     .split(".")[0]] += 1
    for label, (n, ops) in regions.items():
        if n >= 20:
            print("  %-14s %5d bundles  %s" % (label, n, " ".join(
                "%s:%d" % kv for kv in ops.most_common(10))))
shutil.rmtree(dump, ignore_errors=True)     # 90 MB of passes
