"""From the profiler's trace to numbers: the one reduction every PR uses.

``read`` turns an ``.xplane.pb`` (read with ``jax.profiler.ProfileData``,
nothing but JAX) into a small plain structure; the ``reduce_*`` functions
work on that structure alone, so they are checked on the trimmed recorded
trace under ``testdata/`` without a chip.

The structure::

    {"devices": {"/device:TPU:0": {"modules": [[name, start_ns, dur_ns]..],
                                   "ops": [[name, start_ns, dur_ns, tag]..]}},
     "host": [[name, start_ns, dur_ns]..]}      # the loop's own spans

``name`` is the HLO instruction's name and ``tag`` the Program op that the
Executor's ``pd<idx>_<type>`` scope names (or ""), looked up by instruction in
the compiled module's ``op_name`` metadata.

Copied from ``paddle_tpu/profiler.py`` (``_iter_device_xla_events``,
``attribute_op_name``, ``_is_async_span``) and ``tools/bench_profile.py``
(``_category``), so that a later edit there cannot move a metric.
"""

import glob
import os
import re

HOST_SPAN_PREFIX = "chipbench."
# a scope of the Executor's in an ``op_name`` path, bare or inside any
# nesting of the wrappers jax puts around a scope it differentiates
# (``jvp(pd3_x)``, ``transpose(jvp(pd3_x))``, ``checkpoint(..)``): the
# name ends at ``/``, at ``)`` or with the path
_PD_SCOPE = re.compile(r"(?<![A-Za-z0-9_])pd(\d+)_([A-Za-z0-9_.]+)(?=[/)]|$)")
_COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)")


def program_op(text):
    """The tag of the innermost ``pd<idx>_<tag>`` scope in an ``op_name``
    path, or "": the same tag whichever pass the op belongs to, since jax
    rewrites a scope inside a region it differentiates (every layer under
    ``recompute()``) to ``jvp(pd..)`` for the re-run and
    ``transpose(jvp(pd..))`` for the backward.

    A part of an op (``ctx.part_scope``: ``pd<idx>_<op>.<part>``) lies
    inside its op's scope and is named after THAT: ``<the enclosing op's
    tag>.<part>``.  In the forward pass the two agree; the backward of a
    ``custom_vjp`` is traced after its op's lowering has returned, and the
    program then names the part after the op it lowered last
    (``jvp(pd37_moe_experts)/while/body/transpose(jvp(
    pd46_elementwise_add.products))`` in the kanana step: PERF.md 7)."""
    found = _PD_SCOPE.findall(text or "")
    if not found:
        return ""
    tag = found[-1][1]
    if "." in tag:
        ops = [t for _, t in found[:-1] if "." not in t]
        if ops:
            return "%s.%s" % (ops[-1], tag.split(".", 1)[1])
    return tag


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def op_names_in(hlo_text):
    """``{HLO instruction name: op_name}`` of a compiled module's text.  The
    trace names device ops by instruction; the scope path that says which
    Program op one belongs to is only in the module's metadata."""
    return dict(_HLO_OP_NAME.findall(hlo_text))


def short_name(event_name):
    """``fusion.12`` of ``%fusion.12 = bf16[..] fusion(..)``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def read(path, op_names=None):
    """The structure above from one ``.xplane.pb``; ``op_names`` (see
    :func:`op_names_in`) gives the ops their tags."""
    from jax.profiler import ProfileData

    op_names = op_names or {}
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        name = short_name(e.name)
                        dev["ops"].append(
                            [name, e.start_ns, e.duration_ns,
                             program_op(op_names.get(name, ""))])
            if dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(HOST_SPAN_PREFIX)]
    out["host"].sort(key=lambda e: e[1])
    return out


# -- intervals ---------------------------------------------------------------

def union(intervals):
    """Sorted disjoint ``[start, end)`` covering the same points."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(intervals):
    return sum(end - start for start, end in intervals)


def subtract(a, b):
    """The part of the disjoint sorted ``a`` that the disjoint sorted ``b``
    does not cover."""
    out = []
    for start, end in a:
        cur = start
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= end:
                break
            if bs > cur:
                out.append([cur, bs])
            cur = max(cur, be)
            if cur >= end:
                break
        if cur < end:
            out.append([cur, end])
    return out


# -- the steady window -------------------------------------------------------

def steady_window(dev):
    """``(lo_ns, hi_ns, steps)`` on one device: from the start of the second
    execution of the step module to the end of its last one.  The step
    module is the one with the most device time; its first execution in
    the trace is left out because the profiler's start held the host up
    before it."""
    by_name = {}
    for name, start, dur in dev["modules"]:
        by_name.setdefault(name, []).append((start, start + dur))
    if not by_name:
        return None
    runs = sorted(max(by_name.values(), key=length))
    if len(runs) < 3:
        return None
    return runs[1][0], runs[-1][1], len(runs) - 1


def is_collective(name):
    return bool(_COLLECTIVE.match(name))


def is_async_span(name):
    """``*-start`` ops (and send/recv): the event lasts while the transfer
    is in flight and overlaps compute, so it is not compute time."""
    head = base_name(name.lstrip("%"))
    return head.endswith("-start") or head in ("send", "recv")


def category(name, tag):
    """A device op's category: collectives by the HLO name, everything else
    by the Program op that the Executor's scope names
    (``tools/bench_profile.py`` ``_category``, with 'collectives' added,
    the fusion pass's ``fused_conv_bn_act`` counted as a convolution, and
    'loss' and 'dropout' folded into 'other' and 'elementwise')."""
    if is_collective(name):
        return "collectives"
    n = re.sub(r"_grad$", "", base_name(tag))
    if not n:
        return "other"
    if "multihead" in n or "flash" in n or n == "softmax":
        return "attention"
    if n.startswith("fused_dropout_add_ln"):
        return "fused-ln-glue"
    if n in ("sum", "scale") or any(
            k in n for k in ("adam", "sgd", "momentum", "lamb", "clip")):
        return "optimizer"
    if n.endswith("_norm") or "_norm_" in n:
        return "norm"
    if n in ("mul", "fc") or "matmul" in n or n.startswith(
            ("conv2d", "conv3d", "depthwise_conv", "fused_conv", "lookup",
             "gather", "embedding")):
        return "matmul/conv"
    if "dropout" in n or n.startswith(
            ("elementwise", "cast", "convert", "relu", "gelu", "tanh",
             "reshape", "transpose")) or n == "add":
        return "elementwise"
    return "other"


def base_name(name):
    """``fused_ln_fwd`` of ``fused_ln_fwd.3``."""
    return re.sub(r"\.\d+$", "", name)


def kernel_of(name, kernels):
    """The kernel an op's HLO name carries (``fused_ln_fwd.3`` is
    ``fused_ln_fwd``; ``jvp_fused_ln_fwd_.7``, the same kernel traced
    through a ``custom_vjp`` rule, keeps its own name), or None.
    ``kernels`` are the ``tpu_custom_call`` names of the compiled step."""
    return base_name(name) if base_name(name) in kernels else None


# -- reductions --------------------------------------------------------------

CONTAINER_OPS = ("while", "conditional", "call")


def containers(ops):
    """``{id(op)}`` of the events that hold other events of their line: a
    ``while`` (``conditional``, ``call``) is an event of its own over the
    events of its body, and a sum over both counts the body twice.  By
    the HLO name and by time: such an op, and the event after it on the
    line begins and ends inside it.  (Time alone will not do: copies,
    empty custom calls and a few fusions overlap other events on the
    chip's traces without holding them.)"""
    found, open_ops = set(), []
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        while open_ops and open_ops[-1][1] + open_ops[-1][2] <= op[1]:
            open_ops.pop()
        if open_ops and op[1] + op[2] <= open_ops[-1][1] + open_ops[-1][2]:
            found.add(id(open_ops[-1]))
        if base_name(op[0]) in CONTAINER_OPS:
            open_ops.append(op)
    return found


def reduce_trace(trace, kernels=()):
    """Everything the per-layer readers take from a trace, per step and
    averaged over the devices::

        steps, window_s, busy_s, idle_share,
        kernel_s: {kernel: seconds per step}, kernel_calls: {kernel: calls
        per step}, category_s: {category: seconds per step},
        tag_s: {Program op, or ~hlo name where none: seconds per step}
        (both without the events that hold others: ``containers``),
        collective_s, collective_exposed_s (per step),
        gaps: [[host span, seconds]..] the ten longest idle gaps

    Returns None where no device ran the step module three times."""
    per_dev = []
    for dev in trace["devices"].values():
        win = steady_window(dev)
        if win is None:
            continue
        lo, hi, steps = win
        ops = [o for o in dev["ops"] if o[1] >= lo and o[1] + o[2] <= hi]
        sync = [o for o in ops if not is_async_span(o[0])]
        busy = union([o[1], o[1] + o[2]] for o in ops)
        coll = union([o[1], o[1] + o[2]] for o in ops
                     if is_collective(o[0]))
        rest = union([o[1], o[1] + o[2]] for o in sync
                     if not is_collective(o[0]))
        kernel_s, kernel_calls, category_s, tag_s = {}, {}, {}, {}
        holds_others = containers(sync)
        for op in sync:
            name, _, dur, tag = op
            k = kernel_of(name, kernels)
            if k:
                kernel_s[k] = kernel_s.get(k, 0.0) + dur / 1e9 / steps
                kernel_calls[k] = kernel_calls.get(k, 0) + 1.0 / steps
            if id(op) in holds_others:
                continue        # its body's events count, not it too
            cat = category(name, tag)
            category_s[cat] = category_s.get(cat, 0.0) + dur / 1e9 / steps
            tag = tag or "~" + base_name(name)
            tag_s[tag] = tag_s.get(tag, 0.0) + dur / 1e9 / steps
        gaps = subtract([[lo, hi]], busy)
        per_dev.append({
            "steps": steps, "window_s": (hi - lo) / 1e9,
            "busy_s": length(busy) / 1e9,
            "kernel_s": kernel_s, "kernel_calls": kernel_calls,
            "category_s": category_s, "tag_s": tag_s,
            "collective_s": length(coll) / 1e9 / steps,
            "collective_exposed_s": length(subtract(coll, rest)) / 1e9
            / steps,
            "gaps": gaps,
        })
    if not per_dev:
        return None

    def mean(key):
        return sum(d[key] for d in per_dev) / len(per_dev)

    def mean_dict(key):
        names = set().union(*(d[key] for d in per_dev))
        return {n: sum(d[key].get(n, 0.0) for d in per_dev) / len(per_dev)
                for n in names}

    out = {k: mean(k) for k in ("window_s", "busy_s", "collective_s",
                                "collective_exposed_s")}
    out["steps"] = per_dev[0]["steps"]
    out["idle_share"] = 1.0 - out["busy_s"] / out["window_s"]
    for key in ("kernel_s", "kernel_calls", "category_s", "tag_s"):
        out[key] = mean_dict(key)
    out["gaps"] = label_gaps(per_dev[0]["gaps"], trace["host"])
    return out


def label_gaps(gaps, host_spans, top=10):
    """The longest idle gaps of one device, each named after the loop's
    span that covers most of it (``feed``, ``dispatch``, ``wait_loss``; or
    ``between_spans``), summed by name."""
    by_name = {}
    for start, end in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        best, best_cover = "between_spans", 0
        for name, s, d in host_spans:
            if s >= end:
                break
            cover = min(end, s + d) - max(start, s)
            if cover > best_cover:
                best, best_cover = name[len(HOST_SPAN_PREFIX):], cover
        by_name[best] = by_name.get(best, 0.0) + (end - start) / 1e9
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
