"""Static per-op and whole-program cost model over the abstract
interpretation (:mod:`.interp`).

In the spirit of XLA's ahead-of-time fusion/memory analysis
(arXiv:2301.13062) and static parallelism-placement cost models
(arXiv:2110.10548): every op gets a :class:`OpCost` — FLOPs, bytes read,
bytes written, and ICI bytes for collectives — and the program gets
totals plus a **liveness-based peak-memory estimate** checked against a
configurable HBM budget.

Conventions (also in README "Static analysis / lint > Analyzer"):

* FLOPs — one multiply-add = 2 FLOPs.  ``mul``/``matmul``/``fc`` are
  ``2·M·K·N`` (+bias adds for fc); ``conv2d`` is
  ``2 · out_numel · Cin·kh·kw``; a generic ``*_grad`` op costs 2x its
  forward; everything else defaults to one FLOP per output element.
* Bytes — dtype-sized reads of every input + writes of every output,
  using LOCAL (per-worker shard) element counts.
* ICI bytes — ring-algorithm transfer volume per worker for an
  ``n``-participant collective of payload ``B`` local bytes:
  allreduce ``2·B·(n-1)/n``; broadcast / allgather / reducescatter /
  all_to_all ``B·(n-1)/n``; p2p ``send_v2``/``recv_v2`` and ``ppermute``
  move exactly ``B``.
* Peak memory — persistables are always resident; a non-persistable
  value is live from its producing op to its last use (fetch targets to
  program end).  ``-1`` dims resolve via ``PADDLE_TPU_ANALYZE_BATCH``.
* HBM budget — ``PADDLE_TPU_HBM_BUDGET`` (bytes; ``K``/``M``/``G``
  suffixes) or ``program._hbm_budget``; the ``peak-memory-over-budget``
  lint check gates on it.
"""

import json
import os

from .interp import interpret_program

__all__ = [
    "OpCost", "CostReport", "estimate_cost", "register_flops",
    "collective_ici_bytes", "dtype_bytes", "parse_size", "hbm_budget",
    "sync_latency_ms", "calibration_factors", "COLLECTIVE_OP_TYPES",
    "P2P_OP_TYPES", "HOST_IO_OP_TYPES", "PlanPrice", "price_plan",
    "price_program", "plan_calibration_factor",
    "PLANNER_CALIBRATION_FAMILY", "OverlapWindow",
    "overlap_window_table", "tier_wire_table",
]

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "float16": 2, "bfloat16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
}


def dtype_bytes(dtype):
    return _DTYPE_BYTES.get(str(dtype), 4)


def parse_size(text):
    """'2G' / '512M' / '16384' -> bytes."""
    s = str(text).strip()
    mult = 1
    if s and s[-1].upper() in "KMGT":
        mult = 1024 ** ("KMGT".index(s[-1].upper()) + 1)
        s = s[:-1]
    return int(float(s) * mult)


def sync_latency_ms():
    """Assumed cost of one device→host sync (``PADDLE_TPU_SYNC_LATENCY_MS``,
    default 1.0 ms) — the knob behind the static dispatch-overhead
    estimate; set it to the deployment's measured round-trip latency."""
    try:
        return float(os.environ.get("PADDLE_TPU_SYNC_LATENCY_MS", "1.0"))
    except ValueError:
        return 1.0


# host-IO op types executed host-side around the jitted step; each one
# is a per-step sync point in the executor's async dispatch loop.
# Derived from the executor's own roster (ops/io_ops.py) so a new host
# op is counted here automatically; NOT `print` — that lowers to
# jax.debug.print inside the jit and never drains the dispatch queue.
from ..ops.io_ops import HOST_IO_OP_TYPES as _EXEC_HOST_IO_OP_TYPES

HOST_IO_OP_TYPES = frozenset(_EXEC_HOST_IO_OP_TYPES)


def calibration_factors():
    """Per-signature predicted-vs-measured calibration factors the
    autotune loop recorded (``{fusion signature: factor}``) — the
    measure-and-learn feedback into this cost model.  The fusion gates
    multiply their predicted deltas by these; ``analyze_program
    --bench-json`` surfaces them so perf PRs can cite how far the
    static model sits from silicon.  Empty when autotune is disabled or
    nothing has been measured."""
    try:
        from ..autotune import calibrations

        return calibrations()
    except Exception:  # pragma: no cover - autotune subsystem broken
        return {}


def hbm_budget(program=None):
    """The configured HBM budget in bytes, or None (check disabled):
    ``program._hbm_budget`` wins over ``PADDLE_TPU_HBM_BUDGET``."""
    if program is not None:
        b = getattr(program, "_hbm_budget", None)
        if b:
            return parse_size(b)
    val = os.environ.get("PADDLE_TPU_HBM_BUDGET", "").strip()
    return parse_size(val) if val else None


# collective op types (the ICI-bytes and schedule-extraction roster);
# symmetric collectives must appear in the same order on every
# participant, p2p ops pair per directed (src, dst) channel
COLLECTIVE_OP_TYPES = frozenset((
    "c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
    "c_allreduce_prod", "allreduce", "c_reduce_sum", "c_broadcast",
    "broadcast", "c_allgather", "c_reducescatter", "c_scatter",
    "all_to_all", "ppermute", "c_fused_allreduce_sum",
    "c_allreduce_quant", "c_allreduce_start",
    "c_hier_reducescatter", "c_hier_allgather",
))
# NOT c_allreduce_wait: the wait half of an overlap pair is a consumer
# barrier with zero wire traffic — the start op already carried the
# full ring volume, and counting the wait would double the ICI bytes
# and fabricate a second rendezvous in the schedule prover
P2P_OP_TYPES = frozenset(("send_v2", "recv_v2"))


def _op_quant_block(op):
    """The quantization block size a ``c_allreduce_quant`` op carries
    (0 = the env/default resolved at run time)."""
    try:
        return int(op.attrs.get("quant_block", 0) or 0)
    except (TypeError, ValueError):
        return 0


def collective_ici_bytes(op_type, payload_bytes, nranks):
    """Ring-algorithm ICI transfer volume per worker (see module doc)."""
    n = max(int(nranks), 1)
    b = payload_bytes
    if n <= 1:
        return 0
    if op_type.startswith("c_allreduce") or op_type == "allreduce" \
            or op_type == "c_fused_allreduce_sum":
        return int(2 * b * (n - 1) / n)
    if op_type in P2P_OP_TYPES or op_type == "ppermute":
        return int(b)
    if op_type in COLLECTIVE_OP_TYPES:
        return int(b * (n - 1) / n)
    return 0


# ---------------------------------------------------------------------------
# FLOP rules
# ---------------------------------------------------------------------------

_FLOP_RULES = {}


def register_flops(op_type):
    """Register ``fn(op, ins, outs) -> flops`` (ins/outs: [AbstractVal])
    as the FLOP rule for ``op_type``; the ``register_check`` idiom."""

    def deco(fn):
        _FLOP_RULES[op_type] = fn
        return fn

    return deco


def _out_numel(outs):
    return sum(v.local_numel or 0 for v in outs)


def _matmul_flops(op, ins, outs):
    # 2·M·K·N from the two operand shapes (last-two-dims contraction)
    if len(ins) < 2 or ins[0].shape is None or ins[1].shape is None:
        return 2 * _out_numel(outs)
    a, b = ins[0].shape, ins[1].shape
    if not a or not b:
        return 2 * _out_numel(outs)
    k = a[-1]
    m = 1
    for d in a[:-1]:
        m *= max(int(d), 1)
    n = 1
    for d in b[1:]:
        n *= max(int(d), 1)
    return 2 * m * max(int(k), 1) * n


register_flops("mul")(_matmul_flops)
register_flops("matmul")(_matmul_flops)


@register_flops("fc")
def _fc_flops(op, ins, outs):
    return _matmul_flops(op, ins, outs) + _out_numel(outs)


@register_flops("conv2d")
def _conv2d_flops(op, ins, outs):
    if len(ins) < 2 or ins[1].shape is None or len(ins[1].shape) != 4:
        return 2 * _out_numel(outs)
    cout, cin, kh, kw = (max(int(d), 1) for d in ins[1].shape)
    return 2 * _out_numel(outs) * cin * kh * kw


@register_flops("softmax")
def _softmax_flops(op, ins, outs):
    return 5 * _out_numel(outs)  # max, sub, exp, sum, div


@register_flops("fused_multihead_attention")
def _fused_mha_flops(op, ins, outs):
    # Q [B,H,Tq,dh], K [B,H,Tk,dh]: two matmuls (4·B·H·Tq·Tk·dh) plus
    # the online-softmax arithmetic (~5 FLOPs per score cell)
    if len(ins) < 2 or not ins[0].shape or not ins[1].shape \
            or len(ins[0].shape) != 4 or len(ins[1].shape) != 4:
        return 2 * _out_numel(outs)
    b, h, tq, dh = (max(int(d), 1) for d in ins[0].shape)
    tk = max(int(ins[1].shape[2]), 1)
    return 4 * b * h * tq * tk * dh + 5 * b * h * tq * tk


@register_flops("fused_dropout_add_ln")
def _fused_ln_flops(op, ins, outs):
    # mask+add+two-pass stats+normalize+affine ≈ 8 FLOPs per element
    return 8 * _out_numel(outs)


@register_flops("fused_bias_act")
def _fused_bias_act_flops(op, ins, outs):
    return 2 * _out_numel(outs)


@register_flops("softmax_with_cross_entropy")
def _softmax_xent_flops(op, ins, outs):
    n = ins[0].local_numel if ins and ins[0].local_numel else \
        _out_numel(outs)
    return 5 * (n or 0)


@register_flops("fused_conv_bn_act")
def _fused_conv_bn_act_flops(op, ins, outs):
    # the conv's 2·out·Cin·kh·kw plus ~8 FLOPs/element of BN stats +
    # normalize/affine/act epilogue (outs[0] is Out; MeanOut/VarOut are
    # [C] noise)
    conv = _conv2d_flops(op, ins, outs[:1])
    epilogue = (outs[0].local_numel or 0) if outs else 0
    return conv + 8 * epilogue


for _t in ("mean", "reduce_mean", "reduce_sum", "reduce_max",
           "reduce_min", "reduce_prod", "sum"):
    register_flops(_t)(
        lambda op, ins, outs: sum(v.local_numel or 0 for v in ins))


@register_flops("c_allreduce_quant")
def _allreduce_quant_flops(op, ins, outs):
    # quantize (absmax/scale/round) + dequant-sum + requant + final
    # dequant ≈ 8 FLOPs per element on top of the wire transfer — the
    # compute tax that lets compute-bound buckets price quant as losing
    return 8 * sum(v.local_numel or 0 for v in ins)


@register_flops("flash_decode_attention")
def _flash_decode_flops(op, ins, outs):
    # Q [B,H,D] (one row) vs the full ring cache [B,H,Tmax,D]: two
    # matvecs (4·B·H·Tmax·dh) plus ~5 FLOPs/score of online softmax.
    # Static analysis charges the Tmax worst case — the mask-to-cursor
    # saving is a runtime property the cost model deliberately ignores
    if len(ins) < 2 or not ins[1].shape or len(ins[1].shape) != 4:
        return 2 * _out_numel(outs)
    b, h, t, dh = (max(int(d), 1) for d in ins[1].shape)
    return 4 * b * h * t * dh + 5 * b * h * t


@register_flops("kv_cache_write")
def _kv_cache_write_flops(op, ins, outs):
    # a dynamic-slice store: moves X's bytes, negligible arithmetic.
    # Charging the cache's numel (the default) would make every decode
    # step look like a full-cache rewrite
    return ins[1].local_numel or 0 if len(ins) > 1 else 0


@register_flops("kv_cache_prefill")
@register_flops("paged_kv_cache_write")
@register_flops("paged_kv_cache_prefill")
def _kv_cache_prefill_flops(op, ins, outs):
    # paged or ring, a cache fill is a scatter of X's rows — the block
    # table adds an [S] (or [L]) index gather, which rounds to zero
    return ins[1].local_numel or 0 if len(ins) > 1 else 0


@register_flops("paged_flash_decode_attention")
def _paged_flash_decode_flops(op, ins, outs):
    # same two matvecs + online softmax as the ring kernel, but the
    # static worst case is the TABLE depth MB·BL (the request's owned
    # blocks), not a monolithic Tmax — paging's capacity win shows up
    # in the cost model as a per-stream, not per-slot, charge.
    # ins: Q [S,H,D], KCache [N,H,BL,D], VCache, Cursor, BlockTable
    # [S,MB]
    if (len(ins) < 5 or not ins[1].shape or len(ins[1].shape) != 4
            or not ins[4].shape or len(ins[4].shape) < 1):
        return 2 * _out_numel(outs)
    _n, h, bl, dh = (max(int(d), 1) for d in ins[1].shape)
    mb = max(int(ins[4].shape[-1]), 1)
    s = max(int(ins[0].shape[0]), 1) if ins[0].shape else 1
    t = mb * bl
    return 4 * s * h * t * dh + 5 * s * h * t


@register_flops("top_k_sampling")
def _top_k_sampling_flops(op, ins, outs):
    # top-k scan + gumbel over k survivors ≈ 2 passes over the logits
    n = ins[0].local_numel if ins and ins[0].local_numel else 0
    return 2 * n


@register_flops("top_p_sampling")
def _top_p_sampling_flops(op, ins, outs):
    # full sort + softmax + cumsum + gumbel ≈ 5 passes over the logits
    n = ins[0].local_numel if ins and ins[0].local_numel else 0
    return 5 * n


def _op_flops(op, ins, outs):
    rule = _FLOP_RULES.get(op.type)
    if rule is not None:
        return int(rule(op, ins, outs))
    if op.type.endswith("_grad"):
        base = _FLOP_RULES.get(op.type[:-len("_grad")])
        if base is not None:
            return 2 * int(base(op, ins, outs))
    if op.type in ("feed", "fetch", "fill_constant", "assign",
                   "c_gen_nccl_id", "c_comm_init", "send_v2", "recv_v2"):
        return 0
    return _out_numel(outs)


# ---------------------------------------------------------------------------
# per-op cost + whole-program report
# ---------------------------------------------------------------------------

class OpCost:
    """Static cost of one op (all byte counts are per-worker/local)."""

    __slots__ = ("record", "flops", "bytes_read", "bytes_written",
                 "ici_bytes", "ring_id", "tier", "group")

    def __init__(self, record, flops, bytes_read, bytes_written,
                 ici_bytes, ring_id=None, tier=None, group=None):
        self.record = record
        self.flops = int(flops)
        self.bytes_read = int(bytes_read)
        self.bytes_written = int(bytes_written)
        self.ici_bytes = int(ici_bytes)
        self.ring_id = ring_id
        # wire tier of a topology-decomposed collective ("ici"/"dcn"/
        # "pod", from the op's `tier` attr) and its subgroup size (from
        # `comm_nranks`); None on flat collectives — the pricer then
        # derives the tier from the ClusterSpec topology, so flat
        # reports stay byte-identical to the pre-topology model
        self.tier = tier
        self.group = group

    def to_dict(self):
        r = self.record
        d = {
            "block_idx": r.block_idx, "op_idx": r.op_idx,
            "op_type": r.op.type, "flops": self.flops,
            "bytes_read": self.bytes_read,
            "bytes_written": self.bytes_written,
            "ici_bytes": self.ici_bytes, "ring_id": self.ring_id,
        }
        if self.tier is not None:
            d["tier"] = self.tier
            d["group"] = self.group
        return d


class OverlapWindow:
    """One start→wait in-flight window of an overlap-scheduled bucket:
    the op coords of the pair, the roofline inputs (FLOPs + HBM bytes)
    of every op scheduled BETWEEN them, and the ring wire volume of the
    collective itself.  :func:`price_plan` hides
    ``min(window compute, wire)`` per window (arXiv 2110.10548's
    compute-vs-wire window model)."""

    __slots__ = ("bucket", "start", "wait", "window_flops",
                 "window_bytes", "wire_bytes", "quant", "var_names",
                 "tier")

    def __init__(self, bucket, start, wait, window_flops, window_bytes,
                 wire_bytes, quant=False, var_names=(), tier=None):
        self.bucket = int(bucket)
        self.start = tuple(start)   # (block_idx, op_idx) of the start
        self.wait = tuple(wait)     # (block_idx, op_idx) of the wait
        self.window_flops = int(window_flops)
        self.window_bytes = int(window_bytes)
        self.wire_bytes = int(wire_bytes)
        self.quant = bool(quant)
        self.var_names = tuple(var_names)
        self.tier = tier  # wire tier the window's ring rides, or None

    def to_dict(self):
        d = {
            "bucket": self.bucket,
            "start": list(self.start), "wait": list(self.wait),
            "window_flops": self.window_flops,
            "window_bytes": self.window_bytes,
            "wire_bytes": self.wire_bytes,
            "quant": self.quant,
            "var_names": list(self.var_names),
        }
        if self.tier is not None:
            d["tier"] = self.tier
        return d


class CostReport:
    """Whole-program totals + the per-op breakdown behind them."""

    def __init__(self, program, op_costs, peak_memory_bytes,
                 persistent_bytes, nranks, batch_size, budget=None,
                 host_sync_points=0, overlap_windows=()):
        self.program = program
        self.op_costs = op_costs
        self.peak_memory_bytes = int(peak_memory_bytes)
        self.persistent_bytes = int(persistent_bytes)
        self.nranks = nranks
        self.batch_size = batch_size
        self.hbm_budget = budget
        # start→wait windows the overlap scheduler opened (empty when
        # the program carries no c_allreduce_start/wait pairs)
        self.overlap_windows = list(overlap_windows)
        # per-step host sync points: host-IO ops the Executor runs
        # around the jitted step (save/load/print) + one for the fetch
        # materialization itself — each drains the async dispatch queue
        self.host_sync_points = int(host_sync_points)

    @property
    def dispatch_overhead_ms(self):
        """Estimated per-step host-sync overhead: ``host_sync_points ×
        PADDLE_TPU_SYNC_LATENCY_MS`` (default 1.0 ms; set it to the
        measured round-trip of the deployment to project the cost of a
        sync-per-step loop)."""
        return self.host_sync_points * sync_latency_ms()

    @property
    def total_flops(self):
        return sum(c.flops for c in self.op_costs)

    @property
    def total_bytes_read(self):
        return sum(c.bytes_read for c in self.op_costs)

    @property
    def total_bytes_written(self):
        return sum(c.bytes_written for c in self.op_costs)

    @property
    def total_ici_bytes(self):
        return sum(c.ici_bytes for c in self.op_costs)

    def ici_bytes_per_ring(self):
        per = {}
        for c in self.op_costs:
            if c.ici_bytes:
                per[c.ring_id] = per.get(c.ring_id, 0) + c.ici_bytes
        return per

    def ici_bytes_per_tier(self, cluster=None):
        """Wire bytes per topology tier.  An op's explicit ``tier``
        attr (stamped by the hierarchical decomposition) wins; flat
        collectives derive their tier from ``cluster``'s topology (the
        ring size vs chips-per-slice), or ``"ici"`` with no topology —
        so a flat report on a flat cluster is all-ICI, exactly the
        pre-topology accounting."""
        per = {}
        for c in self.op_costs:
            if not c.ici_bytes:
                continue
            tier = _op_tier(c, cluster, self.nranks)
            per[tier] = per.get(tier, 0) + c.ici_bytes
        return per

    @property
    def over_budget(self):
        return (self.hbm_budget is not None
                and self.peak_memory_bytes > self.hbm_budget)

    def to_dict(self):
        return {
            "total_flops": self.total_flops,
            "total_bytes_read": self.total_bytes_read,
            "total_bytes_written": self.total_bytes_written,
            "total_ici_bytes": self.total_ici_bytes,
            "ici_bytes_per_ring": {
                str(k): v for k, v in self.ici_bytes_per_ring().items()},
            "peak_memory_bytes": self.peak_memory_bytes,
            "persistent_bytes": self.persistent_bytes,
            "host_sync_points": self.host_sync_points,
            "dispatch_overhead_ms": self.dispatch_overhead_ms,
            "hbm_budget": self.hbm_budget,
            "nranks": self.nranks,
            "batch_size": self.batch_size,
            "overlap_windows": [w.to_dict()
                                for w in self.overlap_windows],
            "per_op": [c.to_dict() for c in self.op_costs],
        }

    def bench_json(self):
        """BENCH-style metric lines (one JSON object per line) so perf
        PRs can cite the static baseline next to measured numbers."""
        unit_suffix = " (static, batch=%d, nranks=%d)" % (
            self.batch_size, self.nranks)
        rows = [
            ("static_program_flops", self.total_flops, "FLOPs"),
            ("static_program_bytes_read", self.total_bytes_read, "bytes"),
            ("static_program_bytes_written", self.total_bytes_written,
             "bytes"),
            ("static_program_ici_bytes", self.total_ici_bytes, "bytes"),
            ("static_program_peak_memory", self.peak_memory_bytes,
             "bytes"),
            ("static_host_sync_points", self.host_sync_points,
             "syncs/step"),
            ("static_dispatch_overhead_ms",
             round(self.dispatch_overhead_ms, 3),
             "ms/step est. (host_sync_points x "
             "PADDLE_TPU_SYNC_LATENCY_MS)"),
        ]
        lines = [
            json.dumps({"metric": m, "value": v, "unit": u + unit_suffix})
            for m, v, u in rows
        ]
        if self.overlap_windows:
            # overlap-aware wire accounting (priced at the module's
            # default cluster numbers; calibration divided out so the
            # lines are byte-stable across autotune state)
            price = price_plan(self, calibration=1.0)
            lines.append(json.dumps({
                "metric": "static_exposed_wire_ms",
                "value": round(price.exposed_wire_ms, 6),
                "unit": "ms/step est." + unit_suffix}))
            lines.append(json.dumps({
                "metric": "static_overlap_fraction",
                "value": round(price.overlap_fraction, 6),
                "unit": "fraction of wire hidden under %d windows"
                        % len(self.overlap_windows) + unit_suffix}))
        factors = calibration_factors()
        if factors:
            # the autotune feedback loop: measured/predicted gain per
            # fusion signature, so readers see how far the static model
            # sits from silicon (and which gates run calibrated)
            lines.append(json.dumps({
                "metric": "autotune_calibration_factors",
                "value": len(factors),
                "unit": "calibrated fusion signatures" + unit_suffix,
                "factors": {k: round(v, 4)
                            for k, v in sorted(factors.items())},
            }))
        return "\n".join(lines)

    def format_table(self, top=12):
        """Human cost/memory table: totals then the top-N ops by FLOPs."""
        lines = [
            "cost model (batch=%d, nranks=%d):"
            % (self.batch_size, self.nranks),
            "  FLOPs           %16d" % self.total_flops,
            "  bytes read      %16d" % self.total_bytes_read,
            "  bytes written   %16d" % self.total_bytes_written,
            "  ICI bytes       %16d  %s" % (
                self.total_ici_bytes,
                " ".join("ring %s: %d" % (r, b) for r, b in
                         sorted(self.ici_bytes_per_ring().items(),
                                key=lambda kv: repr(kv[0])))),
            "  peak memory     %16d  (persistables %d%s)" % (
                self.peak_memory_bytes, self.persistent_bytes,
                ", budget %d %s" % (
                    self.hbm_budget,
                    "EXCEEDED" if self.over_budget else "ok")
                if self.hbm_budget is not None else ""),
            "  host syncs/step %16d  (est. %.1f ms dispatch overhead)"
            % (self.host_sync_points, self.dispatch_overhead_ms),
        ]
        ranked = sorted(self.op_costs, key=lambda c: -c.flops)[:top]
        if ranked and ranked[0].flops:
            lines.append("  top ops by FLOPs:")
            for c in ranked:
                if not c.flops:
                    break
                r = c.record
                lines.append(
                    "    block %d op %3d %-22s %12d FLOPs %10d B"
                    % (r.block_idx, r.op_idx, r.op.type, c.flops,
                       c.bytes_read + c.bytes_written))
        return "\n".join(lines)


def _val_bytes(v):
    n = v.local_numel
    if n is None:
        return 0
    return n * dtype_bytes(v.dtype)


def estimate_cost(program, interp=None, targets=(), nranks=None,
                  batch_size=None, budget=None):
    """Run the cost model; returns a :class:`CostReport`.

    ``interp``: reuse an existing :func:`interpret_program` result.
    ``targets``: fetch targets kept live to program end for the peak-
    memory estimate.  ``budget``: HBM budget override in bytes (default
    :func:`hbm_budget`).
    """
    if interp is None:
        interp = interpret_program(program, nranks=nranks,
                                   batch_size=batch_size)
    if budget is None:
        budget = hbm_budget(program)
    nranks = interp.nranks

    op_costs = []
    for rec in interp.records:
        op = rec.op
        bytes_read = sum(_val_bytes(v) for v in rec.ins)
        bytes_written = sum(_val_bytes(v) for v in rec.outs)
        ici = 0
        ring = None
        tier = None
        group = None
        if op.type in COLLECTIVE_OP_TYPES or op.type in P2P_OP_TYPES:
            ring = op.attrs.get("ring_id")
            # a topology-decomposed collective runs on a SUBGROUP of
            # the axis (the slice ring or the cross-slice ring): its
            # `comm_nranks` attr carries the subgroup size the ring
            # formula must use, and `tier` names the wire it rides
            tier = op.attrs.get("tier")
            try:
                group = int(op.attrs.get("comm_nranks") or 0) or None
            except (TypeError, ValueError):
                group = None
            participants = group or nranks
            if op.type in ("c_fused_allreduce_sum",
                           "c_hier_reducescatter") \
                    or (op.type == "c_allreduce_start"
                        and not op.attrs.get("quant")):
                # bucketed allreduce: the coalesced buffer carries the
                # SUM of the member payloads in one launch (the async
                # start half carries the same volume at its hoisted
                # position; the wait half is a zero-byte barrier).
                # Same rule for the hierarchical reduce-scatter: the
                # slice ring moves the whole coalesced bucket once
                payload = sum(_val_bytes(v) for v in rec.ins)
            elif op.type == "c_allreduce_quant" \
                    or op.type == "c_allreduce_start":
                # quantized bucket: the wire carries int8 elements plus
                # the f32-per-block scale sidecar, not the member dtype
                from ..quant.collective import quantized_wire_bytes

                numel = sum(v.local_numel or 0 for v in rec.ins)
                payload, _ = quantized_wire_bytes(
                    numel, participants,
                    block=_op_quant_block(op) or None)
            elif op.type == "c_hier_allgather":
                # the gather-back reassembles the full bucket from the
                # per-rank chunks: volume is the OUTPUT member total
                payload = sum(_val_bytes(v) for v in rec.outs)
            else:
                payload = max(
                    [_val_bytes(v) for v in (rec.ins or rec.outs)] or [0])
            if op.type == "recv_v2" and rec.outs:
                payload = _val_bytes(rec.outs[0])
            ici = collective_ici_bytes(op.type, payload, participants)
        op_costs.append(OpCost(
            rec, _op_flops(op, rec.ins, rec.outs), bytes_read,
            bytes_written, ici, ring_id=ring, tier=tier, group=group))

    # ---- overlap windows (start→wait pairs by overlap_bucket id) ----
    windows = []
    open_starts = {}
    for i, c in enumerate(op_costs):
        op = c.record.op
        bucket = op.attrs.get("overlap_bucket")
        if bucket is None:
            continue
        if op.type == "c_allreduce_start":
            open_starts[int(bucket)] = i
        elif op.type == "c_allreduce_wait" \
                and int(bucket) in open_starts:
            si = open_starts.pop(int(bucket))
            inner = op_costs[si + 1:i]
            start = op_costs[si]
            windows.append(OverlapWindow(
                bucket=int(bucket),
                start=(start.record.block_idx, start.record.op_idx),
                wait=(c.record.block_idx, c.record.op_idx),
                window_flops=sum(x.flops for x in inner),
                window_bytes=sum(x.bytes_read + x.bytes_written
                                 for x in inner),
                wire_bytes=start.ici_bytes,
                quant=bool(start.record.op.attrs.get("quant")),
                var_names=start.record.op.outputs.get("Out", ()),
                tier=start.tier))
    windows.sort(key=lambda w: (w.start, w.bucket))

    # ---- liveness-based peak memory ----
    # interval per non-persistable var: [def index, last read index];
    # feeds start live at 0; targets stay live to the end
    target_names = {getattr(t, "name", t) for t in (targets or ())}
    first_def = {}
    last_use = {}
    # every persistable is scope-resident whether or not an op touches
    # it this step (params, optimizer state, snapshots)
    persist = {n: v for n, v in interp.env.items() if v.persistable}
    for rec in interp.records:
        for v in rec.ins:
            if v.persistable:
                continue
            first_def.setdefault(v.name, 0)   # fed/root value
            last_use[v.name] = rec.index
        for v in rec.outs:
            if v.persistable:
                continue
            first_def.setdefault(v.name, rec.index)
            last_use.setdefault(v.name, rec.index)
    end = len(interp.records)
    for n in target_names:
        if n in first_def:
            last_use[n] = end
    persistent_bytes = sum(_val_bytes(v) for v in persist.values())
    # sweep: delta array of byte changes at each op index
    deltas = [0] * (end + 2)
    for n, d0 in first_def.items():
        v = interp.env.get(n)
        if v is None:
            continue
        b = _val_bytes(v)
        deltas[d0] += b
        deltas[last_use.get(n, d0) + 1] -= b
    peak_live = 0
    running = 0
    for d in deltas:
        running += d
        peak_live = max(peak_live, running)
    peak = persistent_bytes + peak_live

    # per-step host sync points: host-IO ops in the global block (the
    # Executor runs them host-side around the jit, draining the async
    # dispatch queue each step) + one sync for materializing the fetch
    # targets themselves (batched — the single-sync-point contract)
    host_syncs = sum(
        1 for op in program.global_block().ops
        if op.type in HOST_IO_OP_TYPES)
    if targets:
        host_syncs += 1

    return CostReport(program, op_costs, peak, persistent_bytes,
                      nranks, interp.batch_size, budget=budget,
                      host_sync_points=host_syncs,
                      overlap_windows=windows)


# ---------------------------------------------------------------------------
# plan pricing — the auto-parallelism planner's entry points
# (arXiv:2110.10548: search placement candidates against a static cost
# model of the hierarchical system)
# ---------------------------------------------------------------------------

# autotune-cache family the planner's predicted-vs-measured step times
# are recorded under (bench.py --child planner writes them); the factor
# multiplies every PlanPrice so plan rankings track measured silicon
PLANNER_CALIBRATION_FAMILY = "planner"


def plan_calibration_factor():
    """measured/predicted step-time factor the autotune loop recorded
    for the planner's own time model (1.0 when autotune is disabled or
    nothing has been measured).  Recorded by ``bench.py --child
    planner`` under the ``planner`` cache family; consumed by
    :func:`price_plan` so every candidate's predicted cost is scaled by
    how far the static model sat from the last measurement."""
    try:
        from ..autotune import calibration_factor, sweep_signature

        return float(calibration_factor(
            sweep_signature(PLANNER_CALIBRATION_FAMILY, {})))
    except Exception:  # pragma: no cover - autotune subsystem broken
        return 1.0


class PlanPrice:
    """Predicted per-step wall time of one parallelism plan candidate.

    Roofline decomposition over the cluster numbers the caller supplies
    (defaults are a generic contemporary TPU chip):

    * ``flops_ms``   — FLOPs / chip peak;
    * ``hbm_ms``     — (bytes read + written) / HBM bandwidth;
    * ``compute_ms`` — max(flops_ms, hbm_ms) × ``schedule_factor``
      (the candidate's schedule inefficiency, e.g. the GPipe bubble
      ``(M+S-1)/M``);
    * ``ici_ms``     — ICI bytes / link bandwidth;
    * ``launch_ms``  — per-collective launch overhead ×
      ``collective_launches`` (how bucketed allreduce wins);
    * ``exposed_wire_ms`` — the overlap-aware wire term: per start→wait
      window the ring transfer hides under ``min(window compute,
      wire)`` of the compute scheduled inside the window, and only the
      remainder (plus all non-window collective traffic) stays on the
      critical path.  With no overlap windows this equals ``ici_ms``
      exactly — the additive model is the degenerate case;
    * ``overlap_fraction`` — hidden wire / total wire (0.0 when nothing
      overlaps);
    * ``step_ms``    — (compute + exposed_wire + launch) ×
      ``calibration`` (:func:`plan_calibration_factor`).

    Absolute numbers are estimates; the planner only needs the RANKING
    to be faithful, and the calibration factor keeps even the absolute
    scale honest once ``bench --child planner`` has measured a step.
    """

    __slots__ = ("flops_ms", "hbm_ms", "compute_ms", "ici_ms",
                 "launch_ms", "step_ms", "ici_bytes",
                 "peak_memory_bytes", "collective_launches",
                 "schedule_factor", "calibration", "exposed_wire_ms",
                 "overlap_fraction", "tier_wire")

    def __init__(self, flops_ms, hbm_ms, compute_ms, ici_ms, launch_ms,
                 step_ms, ici_bytes, peak_memory_bytes,
                 collective_launches, schedule_factor, calibration,
                 exposed_wire_ms=None, overlap_fraction=0.0,
                 tier_wire=None):
        self.flops_ms = flops_ms
        self.hbm_ms = hbm_ms
        self.compute_ms = compute_ms
        self.ici_ms = ici_ms
        self.launch_ms = launch_ms
        self.step_ms = step_ms
        self.ici_bytes = int(ici_bytes)
        self.peak_memory_bytes = int(peak_memory_bytes)
        self.collective_launches = int(collective_launches)
        self.schedule_factor = schedule_factor
        self.calibration = calibration
        self.exposed_wire_ms = (ici_ms if exposed_wire_ms is None
                                else exposed_wire_ms)
        self.overlap_fraction = overlap_fraction
        # {tier: {"bytes": int, "ms": float}} when tiered pricing ran;
        # None on a flat cluster — to_dict() omits the key then, so
        # flat plans serialize byte-identically to the pre-topology
        # planner (the back-compat contract)
        self.tier_wire = tier_wire

    def to_dict(self, canonical=False):
        """``canonical=True`` divides the calibration factor back out
        of ``step_ms`` and reports calibration 1.0 — the byte-stable
        form the planner's determinism contract serializes (a cached
        calibration scales every candidate alike, so the CHOICE is
        invariant, and the canonical bytes must be too)."""
        cal = (self.calibration
               if canonical and self.calibration else None)
        d = {
            "step_ms": round(self.step_ms / cal if cal
                             else self.step_ms, 6),
            "flops_ms": round(self.flops_ms, 6),
            "hbm_ms": round(self.hbm_ms, 6),
            "compute_ms": round(self.compute_ms, 6),
            "ici_ms": round(self.ici_ms, 6),
            "launch_ms": round(self.launch_ms, 6),
            "exposed_wire_ms": round(self.exposed_wire_ms, 6),
            "overlap_fraction": round(self.overlap_fraction, 6),
            "ici_bytes": self.ici_bytes,
            "peak_memory_bytes": self.peak_memory_bytes,
            "collective_launches": self.collective_launches,
            "schedule_factor": round(self.schedule_factor, 6),
            "calibration": 1.0 if canonical
            else round(self.calibration, 6),
        }
        if self.tier_wire is not None:
            d["tier_wire"] = {
                t: {"bytes": int(v["bytes"]),
                    "ms": round(v["ms"], 6)}
                for t, v in sorted(self.tier_wire.items())}
        return d

    def __repr__(self):
        return ("PlanPrice(step=%.3fms compute=%.3f ici=%.3f "
                "launch=%.3f peak=%dB)") % (
            self.step_ms, self.compute_ms, self.ici_ms, self.launch_ms,
            self.peak_memory_bytes)


def _op_tier(c, cluster, nranks):
    """Wire tier of one collective :class:`OpCost`: the op's explicit
    ``tier`` attr (stamped by the hierarchical decomposition) wins;
    otherwise the cluster topology decides by ring size — a flat
    collective over more ranks than fit one slice rides the slow tier."""
    if c.tier:
        return c.tier
    tier_for = getattr(cluster, "tier_for", None)
    if tier_for is None:
        return "ici"
    return tier_for(c.group or nranks or 1)


def _tier_rates(cluster, ici_gbps, launch_us):
    """``{tier: (gbps, launch_us)}``: the caller's explicit ici numbers
    stay authoritative for the fast tier; the slow tiers come from the
    cluster topology."""
    rates = {"ici": (ici_gbps, launch_us)}
    wire = getattr(cluster, "tier_wire", None)
    if wire is not None:
        for t, v in wire().items():
            if t != "ici":
                rates[t] = v
    return rates


def price_plan(report, peak_tflops=100.0, hbm_gbps=1200.0,
               ici_gbps=100.0, launch_us=5.0, schedule_factor=1.0,
               collective_launches=None, calibration=None,
               extra_ici_bytes=0, extra_launches=0, cluster=None,
               extra_tier_bytes=None, tier_launches=None):
    """Price one worker's :class:`CostReport` against cluster numbers;
    returns a :class:`PlanPrice`.  ``collective_launches`` overrides
    the launch count (the planner models allreduce bucketing this way
    without rewriting the program); ``extra_ici_bytes`` /
    ``extra_launches`` charge traffic the program IR does not carry as
    ops (the planner's ZeRO-1 candidates pay their per-step
    param-allgather here); ``calibration`` overrides
    :func:`plan_calibration_factor`.

    **Tiered wire pricing** engages when ``cluster`` declares a
    topology (``ClusterSpec.has_topology``), when the report carries
    tier-stamped ops, or when the caller passes per-tier deltas: each
    collective is assigned a tier (:func:`_op_tier`), wire time is
    summed per tier at that tier's bandwidth, slow-tier launches pay
    the tier's launch latency, and overlap windows hide wire at their
    own tier's rate.  ``extra_tier_bytes`` (``{tier: ±bytes}``) and
    ``tier_launches`` (``{tier: count}`` — an explicit slow-tier launch
    count overriding the per-op tally) are how the planner prices a
    hierarchical decomposition without rewriting the program.  With a
    flat/absent cluster and no tier inputs the flat single-tier
    arithmetic runs unchanged — bit-identical prices, the kill-switch
    contract."""
    if collective_launches is None:
        collective_launches = sum(
            1 for c in report.op_costs if c.ici_bytes > 0)
    collective_launches += int(extra_launches)
    if calibration is None:
        calibration = plan_calibration_factor()
    flops_ms = report.total_flops / (max(peak_tflops, 1e-9) * 1e9)
    hbm_ms = (report.total_bytes_read + report.total_bytes_written) \
        / (max(hbm_gbps, 1e-9) * 1e6)
    compute_ms = max(flops_ms, hbm_ms) * schedule_factor

    tiered = (bool(getattr(cluster, "has_topology", False))
              or bool(extra_tier_bytes) or bool(tier_launches)
              or any(c.tier for c in report.op_costs))
    tier_wire = None
    tier_surcharge_ms = 0.0
    if not tiered:
        ici_bytes = report.total_ici_bytes + int(extra_ici_bytes)
        ici_ms = ici_bytes / (max(ici_gbps, 1e-9) * 1e6)

        def _wire_ms(w):
            return w.wire_bytes / (max(ici_gbps, 1e-9) * 1e6)
    else:
        rates = _tier_rates(cluster, ici_gbps, launch_us)

        def _rate(t):
            return rates.get(t, rates["ici"])

        tier_bytes = {}
        tier_ops = {}
        for c in report.op_costs:
            if c.ici_bytes <= 0:
                continue
            t = _op_tier(c, cluster, report.nranks)
            tier_bytes[t] = tier_bytes.get(t, 0) + c.ici_bytes
            tier_ops[t] = tier_ops.get(t, 0) + 1
        if extra_ici_bytes:
            tier_bytes["ici"] = (tier_bytes.get("ici", 0)
                                 + int(extra_ici_bytes))
        for t, b in sorted((extra_tier_bytes or {}).items()):
            tier_bytes[t] = max(tier_bytes.get(t, 0) + int(b), 0)
        ici_bytes = sum(tier_bytes.values())
        ici_ms = sum(b / (max(_rate(t)[0], 1e-9) * 1e6)
                     for t, b in tier_bytes.items())
        tier_wire = {t: {"bytes": int(b),
                         "ms": b / (max(_rate(t)[0], 1e-9) * 1e6)}
                     for t, b in tier_bytes.items()}
        # slow-tier launch surcharge: a DCN/pod collective pays that
        # tier's launch latency, not the fast tier's.  The per-op tally
        # is capped by the (possibly bucketed) launch override — a
        # bucketed ring launches `collective_launches` times total, so
        # no more than that many can be slow
        for t, (gbps, t_launch) in sorted(rates.items()):
            if t == "ici" or t_launch <= launch_us:
                continue
            if tier_launches is not None:
                count = int(tier_launches.get(t, 0))
            else:
                count = min(tier_ops.get(t, 0), collective_launches)
            tier_surcharge_ms += count * (t_launch - launch_us) / 1000.0

        def _wire_ms(w):
            t = w.tier or _op_tier(
                _WindowTierProbe(w), cluster, report.nranks)
            return w.wire_bytes / (max(_rate(t)[0], 1e-9) * 1e6)

    launch_ms = (collective_launches * launch_us / 1000.0
                 + tier_surcharge_ms)
    # overlap-aware wire term: each start→wait window hides up to its
    # own compute under the ring transfer (max(compute, wire) per
    # window == compute + exposed remainder); everything outside a
    # window — including extra_ici_bytes like the ZeRO-1 allgather —
    # stays fully exposed.  No windows → exposed == ici_ms exactly.
    hidden_ms = 0.0
    for w in getattr(report, "overlap_windows", None) or ():
        wire_ms = _wire_ms(w)
        win_compute_ms = max(
            w.window_flops / (max(peak_tflops, 1e-9) * 1e9),
            w.window_bytes / (max(hbm_gbps, 1e-9) * 1e6))
        hidden_ms += min(win_compute_ms, wire_ms)
    exposed_wire_ms = max(ici_ms - hidden_ms, 0.0)
    overlap_fraction = (hidden_ms / ici_ms) if ici_ms > 0 else 0.0
    step_ms = (compute_ms + exposed_wire_ms + launch_ms) * calibration
    return PlanPrice(flops_ms, hbm_ms, compute_ms, ici_ms, launch_ms,
                     step_ms, ici_bytes,
                     report.peak_memory_bytes, collective_launches,
                     schedule_factor, calibration,
                     exposed_wire_ms=exposed_wire_ms,
                     overlap_fraction=overlap_fraction,
                     tier_wire=tier_wire)


class _WindowTierProbe:
    """Adapter giving an :class:`OverlapWindow` the ``tier``/``group``
    shape :func:`_op_tier` reads — a tier-less window's ring spans the
    full worker set, so its tier derives from the cluster topology."""

    __slots__ = ("tier", "group")

    def __init__(self, w):
        self.tier = w.tier
        self.group = None


def price_program(program, cluster=None, nranks=None, targets=(),
                  batch_size=None, shard_overrides=None,
                  schedule_factor=1.0, collective_launches=None,
                  budget=None, calibration=None):
    """One-call plan pricing: interpret ``program`` (optionally with
    :func:`~.interp.interpret_program` ``shard_overrides`` candidate
    seeding), run the cost model, and price against ``cluster`` — any
    object with ``peak_tflops`` / ``hbm_gbps`` / ``ici_gbps`` /
    ``launch_us`` / ``hbm_bytes`` attributes (the planner's
    ``ClusterSpec``), or None for the module defaults.  Returns
    ``(CostReport, PlanPrice)``."""
    interp = interpret_program(program, nranks=nranks,
                               batch_size=batch_size,
                               shard_overrides=shard_overrides)
    if budget is None:
        budget = getattr(cluster, "hbm_bytes", None) \
            if cluster is not None else hbm_budget(program)
    report = estimate_cost(program, interp=interp, targets=targets,
                           budget=budget)
    price = price_plan(
        report,
        peak_tflops=getattr(cluster, "peak_tflops", 100.0),
        hbm_gbps=getattr(cluster, "hbm_gbps", 1200.0),
        ici_gbps=getattr(cluster, "ici_gbps", 100.0),
        launch_us=getattr(cluster, "launch_us", 5.0),
        schedule_factor=schedule_factor,
        collective_launches=collective_launches,
        calibration=calibration,
        cluster=cluster)
    return report, price


def tier_wire_table(report, cluster):
    """Per-ring wire rows of the topology-tiered accounting — the
    ``analyze_program --plan`` table and the bench hierarchy gate read
    these.  Each row: ring id, the tier that ring rides, total wire
    bytes, the wire ms at that tier's bandwidth, and whether the ring's
    payload travels quantized (any int8-wire op on the ring)."""
    rates = _tier_rates(cluster,
                        getattr(cluster, "ici_gbps", 100.0),
                        getattr(cluster, "launch_us", 5.0))
    per_ring = {}
    for c in report.op_costs:
        if c.ici_bytes <= 0:
            continue
        row = per_ring.setdefault(
            c.ring_id, {"bytes": 0, "quant": False, "tier": None})
        row["bytes"] += c.ici_bytes
        op = c.record.op
        if op.type == "c_allreduce_quant" or op.attrs.get("quant"):
            row["quant"] = True
        t = _op_tier(c, cluster, report.nranks)
        # rings are tier-homogeneous by construction; the slowest op
        # wins if a hand-built program mixes them
        order = ("ici", "dcn", "pod")
        if row["tier"] is None or (t in order and row["tier"] in order
                                   and order.index(t)
                                   > order.index(row["tier"])):
            row["tier"] = t
    rows = []
    for ring in sorted(per_ring, key=lambda r: (r is None, repr(r))):
        row = per_ring[ring]
        tier = row["tier"] or "ici"
        gbps = rates.get(tier, rates["ici"])[0]
        rows.append({
            "ring": ring,
            "tier": tier,
            "bytes": int(row["bytes"]),
            "ms": round(row["bytes"] / (max(gbps, 1e-9) * 1e6), 6),
            "quant": bool(row["quant"]),
        })
    return rows


def overlap_window_table(report, peak_tflops=100.0, hbm_gbps=1200.0,
                         ici_gbps=100.0):
    """Per-window pricing rows for the overlap windows a
    :class:`CostReport` carries — the ``analyze_program --overlap``
    table and the bench gate both read these.  Each row: bucket id,
    start/wait op coords, the window's roofline compute ms, the ring
    wire ms, the exposed remainder, and a verdict (``hidden`` /
    ``partial`` / ``exposed``)."""
    rows = []
    for w in report.overlap_windows:
        wire_ms = w.wire_bytes / (max(ici_gbps, 1e-9) * 1e6)
        compute_ms = max(
            w.window_flops / (max(peak_tflops, 1e-9) * 1e9),
            w.window_bytes / (max(hbm_gbps, 1e-9) * 1e6))
        hidden = min(compute_ms, wire_ms)
        exposed = wire_ms - hidden
        if wire_ms <= 0 or exposed <= wire_ms * 1e-6:
            verdict = "hidden"
        elif hidden > 0:
            verdict = "partial"
        else:
            verdict = "exposed"
        rows.append({
            "bucket": w.bucket,
            "start": list(w.start), "wait": list(w.wait),
            "vars": len(w.var_names),
            "quant": w.quant,
            "window_compute_ms": round(compute_ms, 6),
            "wire_ms": round(wire_ms, 6),
            "exposed_ms": round(exposed, 6),
            "verdict": verdict,
        })
    return rows
