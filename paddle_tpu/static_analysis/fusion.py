"""Cost-guided Program-IR fusion pass pipeline — the TPU-native
realization of Fluid's ``BuildStrategy.fuse_*`` graph passes
(``fuse_elewise_add_act_pass``, ``fuse_all_reduce_op_pass``; XLA's own
per-parameter fusion stands in for ``fuse_optimizer_ops_pass``) plus the
attention/softmax-xent fusions the reference keeps as hand-written
``operators/fused/`` kernels.

XLA fuses instruction-level chains on its own, but it demonstrably
leaves two classes of rewrite on the table (Operator Fusion in XLA,
arXiv:2301.13062): *algorithmic* fusions that change the memory-access
schedule (FlashAttention's blocked online softmax, the one-pass
dropout+residual+layer_norm kernel) and *collective* coalescing
(bucketed gradient allreduce, EQuARX arXiv:2506.17615).  This module
pattern-matches those subgraphs on the Program IR via the PR-1 def-use
machinery and rewrites them in place — each family gated by the PR-3
cost model so a rewrite only fires when the predicted FLOP/byte or ICI
schedule improves:

========================  ==================================================
family                    rewrite
========================  ==================================================
``attention``             matmul(QKᵀ·α) → (+bias) → softmax → (dropout) →
                          matmul(·V) ⇒ one ``fused_multihead_attention``
                          (the Pallas flash kernel on TPU); gated on the
                          measured flash engagement threshold
                          (``PADDLE_TPU_FLASH_MIN_T`` — below it XLA's
                          unblocked attention wins, r05 sweep)
``dropout_add_ln``        (dropout) → elementwise_add → layer_norm ⇒ one
                          ``fused_dropout_add_ln`` (one VMEM pass instead
                          of three HBM round trips)
``bias_act``              elementwise_add(·, 1-D bias) → activation ⇒
                          ``fused_bias_act`` (Fluid's
                          fuse_elewise_add_act_pass; program-level parity,
                          bit-exact composite)
``softmax_xent``          softmax → cross_entropy ⇒ one numerically-stable
                          ``softmax_with_cross_entropy`` (logsumexp form;
                          loss differs from the eps-guarded unfused pair
                          by ~1e-6 relative — documented, not bit-exact)
``conv_bn_act``           conv2d → batch_norm → (act) ⇒ one
                          ``fused_conv_bn_act`` (XLA keeps the MXU conv
                          schedule; the BN+act epilogue is one Pallas
                          VMEM pass — the ResNet-50 MFU 0.250-vs-0.381
                          gap); gated by predicted HBM savings x the
                          autotune calibration factor
``allreduce``             per-grad ``c_allreduce_sum`` ⇒ size-capped
                          ``c_fused_allreduce_sum`` buckets
                          (``PADDLE_TPU_ALLREDUCE_BUCKET_MB``), keeping
                          the PR-3 "optimizer-consumed grads only"
                          semantics and ring conventions
========================  ==================================================

Training programs are rewritten **with their grad twins**: every grad op
carries ``__fwd_op_id__`` (framework.py), so the matcher locates the
backward chain of a matched forward subgraph exactly and replaces it
with the fused op's single ``<type>_grad`` (derived via ``jax.vjp`` over
the fused lowering — registry.generic_grad_fn — which recomputes with
the SAME deterministic RNG stream, so in-kernel dropout masks reproduce).

Every rewrite is bracketed by ``verify_pass`` when pass verification is
enabled (on in tests), and the fused ops are visible to the analyzer:
cost rules in :mod:`.cost`, sharding transfers in :mod:`.interp`, and
the collective-schedule deadlock proof in :mod:`.distributed` all
understand them.

Kill switch: ``PADDLE_TPU_FUSION=0`` disables the whole pipeline.
Introspection: ``CompiledProgram.fusion_report()`` lists applied
rewrites with op coordinates and predicted deltas, plus every matched-
but-skipped pattern with the cost-model reason (also surfaced as the
``fusible-pattern-not-fused`` advisory lint check).
"""

import os

from ..ops.registry import EMPTY_VAR_NAME
from .cost import dtype_bytes

__all__ = [
    "FusionConfig", "FusionRewrite", "FusionSkip", "FusionReport",
    "fusion_enabled", "allreduce_bucket_mb", "apply_fusion_passes",
    "resolve_fused_program", "scan_fusible_patterns",
    "conv_bn_min_bytes",
    "FUSED_FORWARD_OP_TYPES",
]

# fused forward op types this pipeline emits (roster for the
# fused-op-missing-grad lint check and for introspection)
FUSED_FORWARD_OP_TYPES = frozenset((
    "fused_multihead_attention", "fused_dropout_add_ln",
    "fused_bias_act", "softmax_with_cross_entropy",
    "fused_conv_bn_act",
    # decode family: emitted by layers.decode_loop/flash_decode, never
    # by a rewrite here — listed so the matchers and the
    # fused-op-missing-grad lint treat it as an already-fused kernel
    # (forward-only by design: generation is inference)
    "flash_decode_attention", "paged_flash_decode_attention",
))

_ACT_TYPES = ("relu", "gelu", "tanh", "sigmoid", "relu6", "leaky_relu",
              "elu", "softplus", "swish")

# program attrs the executor/analyzer read that Program.clone() does not
# carry — the fused clone must behave identically to the original.
# WARNING: any NEW behavior-bearing Program/Variable attr must be added
# to these lists, or it silently vanishes on the clone the executor
# actually runs whenever a fusion family fires (fusion-off still works,
# which makes the divergence easy to miss)
_PROGRAM_MARKS = ("_num_trainers", "_trainer_id", "_host_tables",
                  "_hbm_budget", "_nan_guard", "_guard_loss_name",
                  "_pipeline_stage", "_guard_abort_after",
                  "_allreduce_bucket_mb", "_shard_optimizer_state",
                  "_quant_buckets", "_overlap", "_hierarchy",
                  "_cluster_spec")

# per-var attrs execution semantics depend on; Program.clone() now
# preserves these itself (framework.CLONE_VAR_MARKS) — this copy pass
# remains for rewrite paths that build vars without clone()
from ..framework import CLONE_VAR_MARKS as _VAR_MARKS  # noqa: E402


def _copy_var_marks(src_program, dst_program):
    for sb, db in zip(src_program.blocks, dst_program.blocks):
        for name, sv in sb.vars.items():
            dv = db.vars.get(name)
            if dv is None:
                continue
            for mark in _VAR_MARKS:
                val = getattr(sv, mark, None)
                if val is not None and not getattr(dv, mark, None):
                    setattr(dv, mark, val)


def fusion_enabled():
    """Global kill switch: ``PADDLE_TPU_FUSION=0`` disables every pass."""
    return os.environ.get("PADDLE_TPU_FUSION", "1") != "0"


def conv_bn_min_bytes():
    """Minimum conv-output bytes the conv+BN+act fusion must save per
    removed op for the rewrite to fire (``PADDLE_TPU_CONV_BN_MIN_BYTES``,
    default 4096 — tiny convs aren't worth an op identity change)."""
    try:
        return int(os.environ.get(
            "PADDLE_TPU_CONV_BN_MIN_BYTES", "4096") or 4096)
    except ValueError:
        return 4096


def _autotune_state():
    """The autotune-cache state token — part of the fusion signature so
    an in-process sweep invalidates resolved program clones whose gates
    used the old calibration."""
    try:
        from ..autotune import state_token

        return state_token()
    except Exception:  # pragma: no cover - autotune subsystem broken
        return ("autotune-unavailable",)


def _calibration(family, **key):
    """(factor, sig, calibrated) for one fusion site: the autotune
    calibration factor the gate multiplies its predicted delta by, the
    signature it looked under, and whether a measured entry existed."""
    try:
        from ..autotune import (autotune_enabled, calibration_factor,
                                lookup, sweep_signature)

        sig = sweep_signature(family, key)
        if not autotune_enabled():
            return 1.0, sig, False
        return calibration_factor(sig), sig, lookup(sig) is not None
    except Exception:  # pragma: no cover - autotune subsystem broken
        return 1.0, str(family), False


def allreduce_bucket_mb(program=None):
    """Gradient-allreduce bucket cap in MB: the program's own
    ``_allreduce_bucket_mb`` mark (how the auto-parallelism planner's
    in-place apply scopes its chosen bucket to ONE program instead of
    leaking a process-global env change), else
    ``PADDLE_TPU_ALLREDUCE_BUCKET_MB``, default 32."""
    mark = getattr(program, "_allreduce_bucket_mb", None) \
        if program is not None else None
    if mark:
        try:
            return float(mark)
        except (TypeError, ValueError):
            pass
    try:
        return float(os.environ.get(
            "PADDLE_TPU_ALLREDUCE_BUCKET_MB", "32") or 32)
    except ValueError:
        return 32.0


class FusionConfig:
    """Which families run — resolved from ``BuildStrategy`` flags (the
    reference's knobs) + the env kill switch."""

    __slots__ = ("enabled", "fuse_attention", "fuse_elewise",
                 "fuse_softmax_xent", "fuse_allreduce", "fuse_conv_bn_act")

    def __init__(self, enabled=None, fuse_attention=True, fuse_elewise=True,
                 fuse_softmax_xent=True, fuse_allreduce=True,
                 fuse_conv_bn_act=True):
        self.enabled = fusion_enabled() if enabled is None else bool(enabled)
        self.fuse_attention = bool(fuse_attention)
        self.fuse_elewise = bool(fuse_elewise)
        self.fuse_softmax_xent = bool(fuse_softmax_xent)
        self.fuse_allreduce = bool(fuse_allreduce)
        self.fuse_conv_bn_act = bool(fuse_conv_bn_act)

    @classmethod
    def default(cls):
        return cls()

    @classmethod
    def from_build_strategy(cls, bs):
        c = cls()
        if bs is None:
            return c
        c.fuse_elewise = bool(getattr(bs, "fuse_elewise_add_act_ops", True))
        c.fuse_allreduce = bool(getattr(bs, "fuse_all_reduce_ops", True))
        c.fuse_attention = bool(getattr(bs, "fuse_attention", True))
        c.fuse_softmax_xent = bool(getattr(bs, "fuse_softmax_xent", True))
        c.fuse_conv_bn_act = bool(getattr(bs, "fuse_bn_act_ops", True))
        return c

    def signature(self, program=None):
        """Hashable identity — part of the executor's jit cache key.

        Pass the program whose rewrite is being keyed: the bucket cap,
        quant threshold, and overlap knob resolve mark → env → default,
        and the MARK must win in the key too — ``allreduce_bucket_mb()``
        bare would record the env value for a program whose
        ``_allreduce_bucket_mb`` mark overrides it, so a plan re-stamp
        (same program version) could hit a stale fused clone built for
        the old bucket size.  Same for ``_overlap``: stamping the mark
        after a resolve must miss the cached clone, or the executor
        keeps running yesterday's schedule."""
        from ..quant.collective import quant_min_bytes as _qmb
        from ..quant.blockwise import quant_block as _qb
        from .hierarchy import hierarchy_signature as _hier
        from .overlap import overlap_enabled as _ov

        return (self.enabled, self.fuse_attention, self.fuse_elewise,
                self.fuse_softmax_xent, self.fuse_allreduce,
                self.fuse_conv_bn_act, allreduce_bucket_mb(program),
                _flash_min_t(), conv_bn_min_bytes(),
                _qmb(program), _qb(), _ov(program), _hier(program),
                _autotune_state())

    def __repr__(self):
        return "FusionConfig%r" % (self.signature(),)


class FusionRewrite:
    """One applied rewrite: family, fused op type, op coordinates of the
    replaced subgraph, and the cost model's predicted deltas."""

    __slots__ = ("family", "fused_op_type", "block_idx", "op_idxs",
                 "vars", "predicted", "note", "inserted")

    def __init__(self, family, fused_op_type, block_idx, op_idxs,
                 vars=(), predicted=None, note="", inserted=1):
        self.family = family
        self.fused_op_type = fused_op_type
        self.block_idx = block_idx
        self.op_idxs = tuple(op_idxs)   # original coordinates (pre-rewrite)
        self.vars = tuple(vars)
        self.predicted = dict(predicted or {})
        self.note = note
        self.inserted = inserted        # fused ops added (fwd [+ grad])

    def to_dict(self):
        return {"family": self.family, "fused_op_type": self.fused_op_type,
                "block_idx": self.block_idx, "op_idxs": list(self.op_idxs),
                "vars": list(self.vars), "predicted": dict(self.predicted),
                "note": self.note, "inserted": self.inserted}

    def __repr__(self):
        pred = ", ".join("%s=%s" % kv for kv in sorted(
            self.predicted.items()))
        return "[%s] block %d ops %s -> %s (%s)%s" % (
            self.family, self.block_idx, list(self.op_idxs),
            self.fused_op_type, pred or "no predicted delta",
            " %s" % self.note if self.note else "")


class FusionSkip:
    """A matched-but-not-rewritten pattern and why (the cost-model or
    structural reason — surfaced by ``fusion_report()`` and by the
    ``fusible-pattern-not-fused`` advisory check)."""

    __slots__ = ("family", "block_idx", "op_idx", "op_type", "reason",
                 "key")

    def __init__(self, family, block_idx, op_idx, op_type, reason,
                 key=None):
        self.family = family
        self.block_idx = block_idx
        self.op_idx = op_idx
        self.op_type = op_type
        self.reason = reason
        self.key = key          # anchor __op_id__ — stable site identity

    def to_dict(self):
        return {"family": self.family, "block_idx": self.block_idx,
                "op_idx": self.op_idx, "op_type": self.op_type,
                "reason": self.reason}

    def __repr__(self):
        return "[%s] block %d op %d (%s) skipped: %s" % (
            self.family, self.block_idx, self.op_idx, self.op_type,
            self.reason)


class FusionReport:
    """Outcome of one pipeline run over one program."""

    def __init__(self, config):
        self.config = config
        self.applied = []
        self.skipped = []

    def record(self, rewrite):
        self.applied.append(rewrite)

    def skip(self, family, op_idx, op_type, reason, block_idx=0,
             key=None):
        entry = FusionSkip(family, block_idx, op_idx, op_type, reason,
                           key=key)
        if key is not None:
            # the family loop re-scans after every applied rewrite and
            # re-encounters still-gated sites: refresh in place (latest
            # coordinates are the ones valid in the reported program)
            # instead of recording the same site N+1 times
            for n, s in enumerate(self.skipped):
                if s.family == family and s.key == key:
                    self.skipped[n] = entry
                    return
        self.skipped.append(entry)

    def counts(self):
        out = {}
        for r in self.applied:
            out[r.family] = out.get(r.family, 0) + 1
        return out

    @property
    def ops_removed(self):
        return sum(len(r.op_idxs) - r.inserted for r in self.applied)

    def to_dict(self):
        return {"config": repr(self.config),
                "applied": [r.to_dict() for r in self.applied],
                "skipped": [s.to_dict() for s in self.skipped],
                "counts": self.counts()}

    def format(self):
        lines = ["fusion report (%d applied, %d skipped; %s)" % (
            len(self.applied), len(self.skipped),
            "enabled" if self.config.enabled else
            "DISABLED (PADDLE_TPU_FUSION=0)")]
        for r in self.applied:
            lines.append("  + %r" % r)
        for s in self.skipped:
            lines.append("  - %r" % s)
        return "\n".join(lines)

    def __repr__(self):
        return self.format()


# ---------------------------------------------------------------------------
# global-block view: consumers/producers/grad twins
# ---------------------------------------------------------------------------

def _is_grad_op(op):
    return op.type.endswith("_grad") \
        or op.attrs.get("op_role") == "backward"


class _GlobalView:
    """Def/use indexes over the global block, rebuilt after every
    rewrite (the fc_fuse_pass lesson: a consumer map built once goes
    stale the moment ops are replaced).  Sub-block closure reads count
    as consumers — fusing away a var a ``while`` body captures would
    leave a dangling read no input slot shows."""

    def __init__(self, program, targets=()):
        self.program = program
        self.block = program.global_block()
        self.targets = {getattr(t, "name", t) for t in (targets or ())}
        self.refresh()

    def refresh(self):
        from .defuse import resolve_sub_block, sub_block_reads_recursive

        block = self.block
        self.consumers = {}    # name -> [(idx, op)]  (all ops)
        self.producers = {}    # name -> [(idx, op)]
        self.closure_reads = set()   # names read inside sub-blocks
        self.grad_twins = {}   # fwd __op_id__ -> [(idx, grad op)]
        self.op_index = {}     # id(op) -> idx
        for idx, op in enumerate(block.ops):
            self.op_index[id(op)] = idx
            for n in op.input_arg_names:
                if n and n != EMPTY_VAR_NAME:
                    self.consumers.setdefault(n, []).append((idx, op))
            for n in op.output_arg_names:
                if n and n != EMPTY_VAR_NAME:
                    self.producers.setdefault(n, []).append((idx, op))
            sub = resolve_sub_block(self.program, op,
                                    host_block_idx=block.idx)
            if sub is not None:
                self.closure_reads.update(
                    sub_block_reads_recursive(self.program, sub))
            fwd_id = op.attrs.get("__fwd_op_id__")
            if fwd_id is not None and _is_grad_op(op):
                self.grad_twins.setdefault(fwd_id, []).append((idx, op))

    def idx_of(self, op):
        return self.op_index[id(op)]

    def shape(self, name):
        v = self.block._find_var_recursive(name)
        return None if v is None else v.shape

    def var(self, name):
        return self.block._find_var_recursive(name)

    def sole_fwd_consumer(self, name):
        """The single forward-op consumer of ``name``, or None when the
        name has 0 or >1 forward consumers, is read by a sub-block, or
        is observable (fetched)."""
        if name in self.targets or name in self.closure_reads:
            return None
        fwd = [(i, o) for i, o in self.consumers.get(name, ())
               if not _is_grad_op(o)]
        if len(fwd) != 1:
            return None
        return fwd[0]

    def unconsumed(self, name, group_ops):
        """True when every consumer of ``name`` is inside ``group_ops``
        (by identity) and the name is neither fetched nor persistable —
        i.e. removing its producer leaves no dangling read."""
        if name in self.targets or name in self.closure_reads:
            return False
        v = self.var(name)
        if v is not None and v.persistable:
            return False
        ids = {id(o) for o in group_ops}
        return all(id(o) in ids for _, o in self.consumers.get(name, ()))

    def twin(self, op, expect_type):
        """The unique grad twin of ``op`` with the expected type, or
        None (no grads).  Returns False when the twin structure is
        unexpected (refuse the match rather than mis-rewrite)."""
        twins = self.grad_twins.get(op.attrs.get("__op_id__"), [])
        twins = [t for t in twins if t[1].type == expect_type]
        if not twins:
            return None
        if len(twins) > 1:
            return False
        return twins[0]


def _replace_ops(block, replacements, removals):
    """Apply a rewrite: ``replacements`` maps op index -> new op;
    ``removals`` is the set of indices to drop."""
    new_ops = []
    for i, op in enumerate(block.ops):
        if i in replacements:
            new_ops.append(replacements[i])
        elif i in removals:
            continue
        else:
            new_ops.append(op)
    block.ops[:] = new_ops
    block.program._bump_version()


def _new_op(block, type, inputs, outputs, attrs):
    """Build a replacement op.  ``block=None`` (dry-run scans) draws the
    op id from the global counter instead of the program's, so a
    side-effect-free scan never shifts the program's deterministic op-id
    sequence (the RNG-reproducibility contract)."""
    from ..framework import Operator

    return Operator(block, type, inputs, outputs, attrs)


def _grad_attrs(fwd_op, extra=None):
    attrs = dict(fwd_op.attrs)
    attrs.pop("__op_id__", None)
    attrs["__fwd_op_id__"] = fwd_op.attrs.get("__op_id__", 0)
    attrs["op_role"] = "backward"
    if extra:
        attrs.update(extra)
    return attrs


def _numel(shape):
    if shape is None:
        return None
    n = 1
    for d in shape:
        if d is not None and int(d) > 0:    # a dynamic dim counts as 1
            n *= int(d)
    return n


def _var_bytes(view, name):
    v = view.var(name)
    if v is None or v.shape is None:
        return 0
    return (_numel(v.shape) or 0) * dtype_bytes(v.dtype)


def _flash_min_t():
    try:
        from ..ops.pallas.flash_attention import flash_min_t

        return flash_min_t()
    except Exception:  # pragma: no cover - jax/pallas unavailable
        return int(os.environ.get("PADDLE_TPU_FLASH_MIN_T", "512") or 512)


# ---------------------------------------------------------------------------
# family: attention
# ---------------------------------------------------------------------------

def _find_attention(view, report, dry_run=False):
    """matmul(QKᵀ·α) → (+bias) → softmax → (dropout) → matmul(·V)."""
    block = view.block
    for i, op in enumerate(block.ops):
        if op.type != "matmul" or _is_grad_op(op):
            continue
        if not op.attrs.get("transpose_Y") or op.attrs.get("transpose_X"):
            continue
        q = op.inputs.get("X", [None])[0]
        k = op.inputs.get("Y", [None])[0]
        qs, ks = view.shape(q), view.shape(k)
        if not qs or not ks or len(qs) != 4 or len(ks) != 4:
            continue
        s0 = op.outputs["Out"][0]
        alpha = float(op.attrs.get("alpha", 1.0))
        group = [op]
        nxt = view.sole_fwd_consumer(s0)
        bias = None
        add_op = None
        if nxt is not None and nxt[1].type == "elementwise_add":
            add_op = nxt[1]
            if add_op.inputs.get("X", [None])[0] != s0:
                continue
            if int(add_op.attrs.get("axis", -1)) != -1:
                continue
            bias = add_op.inputs.get("Y", [None])[0]
            bs = view.shape(bias)
            # the fused op broadcasts its bias per BATCH over heads and
            # query rows — only the [B,1,1,Tk] form (or [1,Tk]) has the
            # same meaning under the unfused add's trailing alignment.
            # A general rank-2 [B,Tk] trailing-aligns to the (Tq,Tk)
            # score dims, i.e. a per-QUERY-ROW bias: different math
            # whenever B==Tq>1, so it must stay unfused.
            if not bs or not (
                    (len(bs) == 4 and bs[1] == 1 and bs[2] == 1)
                    or (len(bs) == 2 and bs[0] == 1)):
                continue
            bvar = view.var(bias)
            # the fused path treats the bias as constant (padding masks
            # are data): a bias that needs a gradient must stay unfused
            bias_twin = view.twin(add_op, "elementwise_add_grad")
            if bias_twin is False:
                continue
            if bias_twin is not None:
                yg = bias_twin[1].outputs.get("Y@GRAD", [EMPTY_VAR_NAME])
                if yg and yg[0] != EMPTY_VAR_NAME:
                    report.skip("attention", i, op.type,
                                "additive bias %r requires a gradient — "
                                "the flash path treats the mask bias as "
                                "constant" % bias,
                                key=op.attrs.get("__op_id__"))
                    continue
            if bvar is None:
                continue
            group.append(add_op)
            nxt = view.sole_fwd_consumer(add_op.outputs["Out"][0])
        if nxt is None or nxt[1].type != "softmax":
            continue
        sm_op = nxt[1]
        ax = int(sm_op.attrs.get("axis", -1))
        if ax not in (-1, 3):
            continue
        group.append(sm_op)
        nxt = view.sole_fwd_consumer(sm_op.outputs["Out"][0])
        drop_op = None
        rate = 0.0
        if nxt is not None and nxt[1].type == "dropout":
            drop_op = nxt[1]
            if drop_op.attrs.get("dropout_implementation") \
                    != "upscale_in_train":
                report.skip("attention", i, op.type,
                            "attention dropout uses downgrade_in_infer — "
                            "the fused kernel implements upscale_in_train "
                            "only", key=op.attrs.get("__op_id__"))
                continue
            mask = drop_op.outputs.get("Mask", [None])[0]
            probe = group + [drop_op]
            if mask and not view.unconsumed(
                    mask, probe + _twin_ops(view, probe)):
                continue
            rate = float(drop_op.attrs.get("dropout_prob", 0.0) or 0.0)
            group.append(drop_op)
            nxt = view.sole_fwd_consumer(drop_op.outputs["Out"][0])
        if nxt is None or nxt[1].type != "matmul":
            continue
        mm2 = nxt[1]
        if mm2.attrs.get("transpose_X") or mm2.attrs.get("transpose_Y") \
                or float(mm2.attrs.get("alpha", 1.0)) != 1.0:
            continue
        probs = (drop_op or sm_op).outputs["Out"][0]
        if mm2.inputs.get("X", [None])[0] != probs:
            continue
        v = mm2.inputs.get("Y", [None])[0]
        vs = view.shape(v)
        if not vs or len(vs) != 4:
            continue
        group.append(mm2)

        # ---- cost gate: the blocked flash kernel only beats XLA's
        # fused unblocked attention above the measured engagement
        # threshold (r05 v5e sweep, env-tunable) ----
        tq = int(qs[2]) if qs[2] and int(qs[2]) > 0 else 0
        tk = int(ks[2]) if ks[2] and int(ks[2]) > 0 else 0
        min_t = _flash_min_t()
        if max(tq, tk) < min_t:
            report.skip(
                "attention", i, op.type,
                "cost model: T=%d below the flash engagement threshold "
                "%d (XLA's unblocked attention is faster there, r05 "
                "sweep; PADDLE_TPU_FLASH_MIN_T re-decides)"
                % (max(tq, tk), min_t),
                key=op.attrs.get("__op_id__"))
            continue

        match = _match_attention_grads(view, report, group, i, q, k, v,
                                       bias, alpha, rate, drop_op, mm2,
                                       dry_run=dry_run)
        if match is None:
            continue
        if dry_run:
            report.record(match["rewrite"])
            continue
        return match
    return None


def _match_attention_grads(view, report, group, i, q, k, v, bias, alpha,
                           rate, drop_op, mm2, dry_run=False):
    mm1, sm_op = group[0], next(o for o in group if o.type == "softmax")
    add_op = next((o for o in group if o.type == "elementwise_add"), None)
    ctx_out = mm2.outputs["Out"][0]

    # grad twins (empty for inference programs)
    twins = []
    for o in group:
        t = view.twin(o, o.type + "_grad")
        if t is False:
            return None
        if t is not None:
            twins.append(t)
    mm2_twin = view.twin(mm2, "matmul_grad")
    mm1_twin = view.twin(mm1, "matmul_grad")
    if twins and (mm2_twin is None or mm1_twin in (None, False)
                  or mm2_twin is False or len(twins) != len(group)):
        # partial backward chain — refuse rather than mis-rewrite
        return None

    # every removed intermediate (and its grad) must be internal
    removed_fwd = [o.outputs["Out"][0] for o in group[:-1]]
    all_group_ops = list(group) + [t[1] for t in twins]
    for n in removed_fwd:
        if not view.unconsumed(n, all_group_ops):
            return None
    if twins:
        for _, g in twins:
            for n in g.output_arg_names:
                if n == EMPTY_VAR_NAME:
                    continue
                # grads the outside world keeps: q/k/v grads survive
                if n in (_grad_out(mm1_twin[1], "X@GRAD"),
                         _grad_out(mm1_twin[1], "Y@GRAD"),
                         _grad_out(mm2_twin[1], "Y@GRAD")):
                    continue
                if not view.unconsumed(n, all_group_ops):
                    return None

    block = view.block
    op_block = None if dry_run else block
    qs, ks = view.shape(q), view.shape(k)
    # a dynamic batch dim is fine (_numel maps it to None) but the
    # head/seq/depth dims must be static: the flash kernel blocks on
    # them, and a mixed case (dynamic Tq, static Tk over the threshold)
    # reaches here past the cost gate
    dyn = [d for d in (qs[1], qs[2], qs[3], ks[2])
           if not (isinstance(d, int) and d > 0)]
    if dyn:
        report.skip(
            "attention", i, mm1.type,
            "dynamic head/seq dims %r — the fused attention kernel "
            "needs static non-batch shapes" % (dyn,),
            key=mm1.attrs.get("__op_id__"))
        return None
    b, h, tq, dh = (_numel((qs[0],)), int(qs[1]), int(qs[2]), int(qs[3]))
    tk = int(ks[2])
    # predicted delta: the [B,H,Tq,Tk] score/prob tensors never touch HBM
    score_bytes = 4 * (b or 1) * h * tq * tk
    n_inter = len(group) - 1
    predicted = {
        "hbm_bytes_saved": 2 * n_inter * score_bytes,
        "ops_removed": len(group) - 1,
        "flash_kernel": "tpu" if max(tq, tk) >= _flash_min_t() else "xla",
    }

    ins = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        ins["BiasQK"] = [bias]
    attrs = {"causal": False, "scale": alpha, "dropout_rate": rate}
    if drop_op is not None and "is_test" in drop_op.attrs:
        attrs["is_test"] = drop_op.attrs["is_test"]
    fused = _new_op(op_block, "fused_multihead_attention", ins,
                    {"Out": [ctx_out]}, attrs)

    replacements = {view.idx_of(mm2): fused}
    removals = {view.idx_of(o) for o in group} - set(replacements)
    if twins:
        g_ins = dict(ins)
        g_ins["Out"] = [ctx_out]
        g_ins["Out@GRAD"] = list(mm2_twin[1].inputs.get(
            "Out@GRAD", [EMPTY_VAR_NAME]))
        g_outs = {
            "Q@GRAD": [_grad_out(mm1_twin[1], "X@GRAD")],
            "K@GRAD": [_grad_out(mm1_twin[1], "Y@GRAD")],
            "V@GRAD": [_grad_out(mm2_twin[1], "Y@GRAD")],
        }
        gfused = _new_op(op_block, "fused_multihead_attention_grad",
                         g_ins, g_outs, _grad_attrs(fused))
        first_twin = min(t[0] for t in twins)
        replacements[first_twin] = gfused
        removals |= {t[0] for t in twins} - set(replacements)

    op_idxs = sorted({view.idx_of(o) for o in group}
                     | {t[0] for t in twins})
    rewrite = FusionRewrite(
        "attention", "fused_multihead_attention", block.idx, op_idxs,
        vars=(q, k, v) + ((bias,) if bias else ()), predicted=predicted,
        note="dropout rate %.3g (mask stream differs from the unfused "
             "dropout op — documented)" % rate if rate else "",
        inserted=len(replacements))
    return {"replacements": replacements, "removals": removals,
            "rewrite": rewrite}


def _grad_out(grad_op, slot):
    names = grad_op.outputs.get(slot, [])
    return names[0] if names else EMPTY_VAR_NAME


# ---------------------------------------------------------------------------
# family: dropout + residual-add + layer_norm
# ---------------------------------------------------------------------------

def _find_dropout_add_ln(view, report, dry_run=False):
    block = view.block
    for i, op in enumerate(block.ops):
        if op.type != "layer_norm" or _is_grad_op(op):
            continue
        x_in = op.inputs.get("X", [None])[0]
        scale = op.inputs.get("Scale", [None])
        bias = op.inputs.get("Bias", [None])
        if not scale or not bias or scale[0] is None or bias[0] is None:
            continue
        xs = view.shape(x_in)
        if not xs or int(op.attrs.get("begin_norm_axis", 1)) \
                != len(xs) - 1:
            continue
        d = xs[-1]
        if d is None or int(d) <= 0:
            continue
        prods = view.producers.get(x_in, [])
        if len(prods) != 1 or prods[0][1].type != "elementwise_add":
            continue
        add_op = prods[0][1]
        sole = view.sole_fwd_consumer(x_in)
        if sole is None or sole[1] is not op:
            continue
        a = add_op.inputs.get("X", [None])[0]
        bm = add_op.inputs.get("Y", [None])[0]
        if view.shape(a) != view.shape(bm):
            continue
        # which side is a dropout output?
        drop_op = None
        x_name, res_name = bm, a
        for cand, other in ((a, bm), (bm, a)):
            p = view.producers.get(cand, [])
            if len(p) == 1 and p[0][1].type == "dropout" \
                    and not _is_grad_op(p[0][1]):
                dp = p[0][1]
                sole = view.sole_fwd_consumer(cand)
                if sole is None or sole[1] is not add_op:
                    continue
                if dp.attrs.get("dropout_implementation") \
                        != "upscale_in_train":
                    continue
                drop_op = dp
                x_name, res_name = dp.inputs["X"][0], other
                break
        rate = 0.0
        group = ([drop_op] if drop_op else []) + [add_op, op]
        if drop_op is not None:
            rate = float(drop_op.attrs.get("dropout_prob", 0.0) or 0.0)
            mask = drop_op.outputs.get("Mask", [None])[0]
            if mask and not view.unconsumed(
                    mask, group + _twin_ops(view, group)):
                continue

        # grad twins
        twins = []
        bad = False
        for o in group:
            t = view.twin(o, o.type + "_grad")
            if t is False:
                bad = True
                break
            if t is not None:
                twins.append((o, t))
        if bad:
            continue
        if twins and len(twins) != len(group):
            continue
        all_ops = group + [t[1][1] for t in twins]
        # removed intermediates: add out (x_in), dropout out, Mean/Var
        removed = [x_in] + ([drop_op.outputs["Out"][0]] if drop_op else [])
        removed += [n for s in ("Mean", "Variance")
                    for n in op.outputs.get(s, []) if n]
        if not all(view.unconsumed(n, all_ops) for n in removed):
            continue
        ln_twin = next((t for o, t in twins if o is op), None)
        add_twin = next((t for o, t in twins if o is add_op), None)
        drop_twin = next((t for o, t in twins if o is drop_op), None)
        if twins:
            internal_grads = []
            internal_grads.append(_grad_out(ln_twin[1], "X@GRAD"))
            if drop_op is not None:
                slot = "Y@GRAD" if add_op.inputs["Y"][0] \
                    == drop_op.outputs["Out"][0] else "X@GRAD"
                internal_grads.append(_grad_out(add_twin[1], slot))
            for n in internal_grads:
                if n != EMPTY_VAR_NAME \
                        and not view.unconsumed(n, all_ops):
                    bad = True
            if bad:
                continue

        n_rows = _numel(xs[:-1])
        predicted = {
            "hbm_bytes_saved": 2 * (len(group) - 1)
            * (n_rows or 1) * int(d) * 4,
            "ops_removed": len(group) - 1,
        }
        fattrs = {"dropout_prob": rate,
                  "epsilon": float(op.attrs.get("epsilon", 1e-5))}
        if drop_op is not None and "is_test" in drop_op.attrs:
            fattrs["is_test"] = drop_op.attrs["is_test"]
        ins = {"X": [x_name], "Residual": [res_name],
               "Scale": [scale[0]], "Bias": [bias[0]]}
        fused = _new_op(None if dry_run else block, "fused_dropout_add_ln", ins,
                        {"Out": [op.outputs["Y"][0]]}, fattrs)
        replacements = {view.idx_of(op): fused}
        removals = {view.idx_of(o) for o in group} - set(replacements)
        if twins:
            if drop_op is not None:
                x_grad = _grad_out(drop_twin[1], "X@GRAD")
                res_slot = "X@GRAD" if add_op.inputs["X"][0] == res_name \
                    else "Y@GRAD"
                res_grad = _grad_out(add_twin[1], res_slot)
            else:
                x_slot = "Y@GRAD" if add_op.inputs["Y"][0] == x_name \
                    else "X@GRAD"
                res_slot = "X@GRAD" if x_slot == "Y@GRAD" else "Y@GRAD"
                x_grad = _grad_out(add_twin[1], x_slot)
                res_grad = _grad_out(add_twin[1], res_slot)
            g_ins = dict(ins)
            g_ins["Out"] = [op.outputs["Y"][0]]
            g_ins["Out@GRAD"] = list(ln_twin[1].inputs.get(
                "Y@GRAD", [EMPTY_VAR_NAME]))
            g_outs = {
                "X@GRAD": [x_grad], "Residual@GRAD": [res_grad],
                "Scale@GRAD": [_grad_out(ln_twin[1], "Scale@GRAD")],
                "Bias@GRAD": [_grad_out(ln_twin[1], "Bias@GRAD")],
            }
            gfused = _new_op(None if dry_run else block, "fused_dropout_add_ln_grad", g_ins,
                             g_outs, _grad_attrs(fused))
            first_twin = min(t[0] for _, t in twins)
            replacements[first_twin] = gfused
            removals |= {t[0] for _, t in twins} - set(replacements)
        op_idxs = sorted({view.idx_of(o) for o in group}
                         | {t[0] for _, t in twins})
        rewrite = FusionRewrite(
            "dropout_add_ln", "fused_dropout_add_ln", block.idx, op_idxs,
            vars=(x_name, res_name), predicted=predicted,
            note=("dropout rate %.3g (mask stream differs from the "
                  "unfused dropout op — documented)" % rate) if rate
            else "rate 0: bit-exact in f32",
            inserted=len(replacements))
        match = {"replacements": replacements, "removals": removals,
                 "rewrite": rewrite}
        if dry_run:
            report.record(rewrite)
            continue
        return match
    return None


def _twin_ops(view, group):
    out = []
    for o in group:
        twins = view.grad_twins.get(o.attrs.get("__op_id__"), [])
        out.extend(t for _, t in twins)
    return out


# ---------------------------------------------------------------------------
# family: bias + activation  (fuse_elewise_add_act_pass)
# ---------------------------------------------------------------------------

def _find_bias_act(view, report, dry_run=False):
    block = view.block
    for i, op in enumerate(block.ops):
        if op.type != "elementwise_add" or _is_grad_op(op):
            continue
        b = op.inputs.get("Y", [None])[0]
        bv = view.var(b) if b else None
        if bv is None or not bv.persistable or bv.shape is None \
                or len(bv.shape) != 1:
            continue
        out = op.outputs["Out"][0]
        nxt = view.sole_fwd_consumer(out)
        if nxt is None or nxt[1].type not in _ACT_TYPES:
            continue
        act_op = nxt[1]
        group = [op, act_op]
        twins = []
        bad = False
        for o in group:
            t = view.twin(o, o.type + "_grad")
            if t is False:
                bad = True
                break
            if t is not None:
                twins.append((o, t))
        if bad or (twins and len(twins) != len(group)):
            continue
        all_ops = group + [t[1][1] for t in twins]
        if not view.unconsumed(out, all_ops):
            continue
        add_twin = next((t for o, t in twins if o is op), None)
        act_twin = next((t for o, t in twins if o is act_op), None)
        if twins:
            inter_grad = _grad_out(act_twin[1], "X@GRAD")
            if inter_grad != EMPTY_VAR_NAME \
                    and not view.unconsumed(inter_grad, all_ops):
                continue
        predicted = {"ops_removed": 1,
                     "hbm_bytes_saved": 2 * _var_bytes(view, out)}
        fattrs = {k: v for k, v in act_op.attrs.items()
                  if not k.startswith("__") and k != "op_namescope"}
        fattrs["act_type"] = act_op.type
        fattrs["axis"] = int(op.attrs.get("axis", -1))
        fused = _new_op(None if dry_run else block, "fused_bias_act",
                        {"X": [op.inputs["X"][0]], "Bias": [b]},
                        {"Out": [act_op.outputs["Out"][0]]}, fattrs)
        replacements = {view.idx_of(act_op): fused}
        removals = {view.idx_of(op)}
        if twins:
            g_ins = {"X": [op.inputs["X"][0]], "Bias": [b],
                     "Out": [act_op.outputs["Out"][0]],
                     "Out@GRAD": list(act_twin[1].inputs.get(
                         "Out@GRAD", [EMPTY_VAR_NAME]))}
            g_outs = {"X@GRAD": [_grad_out(add_twin[1], "X@GRAD")],
                      "Bias@GRAD": [_grad_out(add_twin[1], "Y@GRAD")]}
            gfused = _new_op(None if dry_run else block, "fused_bias_act_grad", g_ins, g_outs,
                             _grad_attrs(fused))
            first_twin = min(t[0] for _, t in twins)
            replacements[first_twin] = gfused
            removals |= {t[0] for _, t in twins} - set(replacements)
        op_idxs = sorted({view.idx_of(o) for o in group}
                         | {t[0] for _, t in twins})
        rewrite = FusionRewrite(
            "bias_act", "fused_bias_act", block.idx, op_idxs,
            vars=(op.inputs["X"][0], b), predicted=predicted,
            note="bit-exact composite (%s)" % act_op.type,
            inserted=len(replacements))
        match = {"replacements": replacements, "removals": removals,
                 "rewrite": rewrite}
        if dry_run:
            report.record(rewrite)
            continue
        return match
    return None


# ---------------------------------------------------------------------------
# family: softmax + cross_entropy
# ---------------------------------------------------------------------------

def _find_softmax_xent(view, report, dry_run=False):
    block = view.block
    for i, op in enumerate(block.ops):
        if op.type != "softmax" or _is_grad_op(op):
            continue
        p_name = op.outputs["Out"][0]
        xs = view.shape(op.inputs["X"][0])
        ax = int(op.attrs.get("axis", -1))
        if xs and ax not in (-1, len(xs) - 1):
            continue
        ce_ops = [(j, o) for j, o in view.consumers.get(p_name, ())
                  if o.type == "cross_entropy" and not _is_grad_op(o)]
        if len(ce_ops) != 1:
            continue
        j, ce = ce_ops[0]
        if ce.inputs.get("X", [None])[0] != p_name:
            continue
        label = ce.inputs.get("Label", [None])[0]
        # the fused op is placed at the softmax's index so consumers of
        # the (still-produced) softmax output between the two sites stay
        # valid — the label must already be defined there
        lv = view.var(label)
        label_ready = lv is not None and (lv.is_data or lv.persistable)
        if not label_ready:
            lp = view.producers.get(label, [])
            label_ready = bool(lp) and all(idx < i for idx, _ in lp)
        if not label_ready:
            report.skip("softmax_xent", i, op.type,
                        "label %r is produced after the softmax — cannot "
                        "hoist the fused op" % label,
                        key=op.attrs.get("__op_id__"))
            continue
        group = [op, ce]
        sm_twin = view.twin(op, "softmax_grad")
        ce_twin = view.twin(ce, "cross_entropy_grad")
        if sm_twin is False or ce_twin is False:
            continue
        twins = [t for t in (ce_twin, sm_twin) if t is not None]
        if twins and len(twins) != 2:
            continue
        all_ops = group + [t[1] for t in twins]
        if twins:
            # the probability grad must be exclusively internal: other
            # consumers of the softmax output (metrics) are fine, but a
            # second grad contribution means a second loss path reads
            # the probabilities — the fused op's Softmax output is
            # stop_gradient and would silently drop it
            pg = _grad_out(ce_twin[1], "X@GRAD")
            if pg == EMPTY_VAR_NAME \
                    or not view.unconsumed(pg, all_ops):
                report.skip(
                    "softmax_xent", i, op.type,
                    "softmax output %r receives gradients from outside "
                    "the cross_entropy — fusing would drop them"
                    % p_name, key=op.attrs.get("__op_id__"))
                continue
            # the fused grad emits Logits@GRAD only: a differentiable
            # soft label (distillation teacher) whose Label@GRAD is
            # read downstream would be left dangling
            lg = _grad_out(ce_twin[1], "Label@GRAD")
            if lg != EMPTY_VAR_NAME and not view.unconsumed(lg, all_ops):
                report.skip(
                    "softmax_xent", i, op.type,
                    "label %r is differentiable and its gradient %r is "
                    "consumed — the fused op emits no Label@GRAD"
                    % (label, lg), key=op.attrs.get("__op_id__"))
                continue
        cs = view.shape(p_name)
        predicted = {
            "ops_removed": 1,
            "hbm_bytes_saved": 2 * _var_bytes(view, p_name),
            "flops_saved": 3 * (_numel(cs) or 0),
        }
        fattrs = {"soft_label": ce.attrs.get("soft_label", False),
                  "ignore_index": int(ce.attrs.get("ignore_index", -100)),
                  "axis": -1}
        fused = _new_op(
            None if dry_run else block, "softmax_with_cross_entropy",
            {"Logits": list(op.inputs["X"]), "Label": [label]},
            {"Softmax": [p_name], "Loss": list(ce.outputs["Y"])}, fattrs)
        replacements = {i: fused}
        removals = {j}
        if twins:
            g_ins = {"Logits": list(op.inputs["X"]), "Label": [label],
                     "Softmax": [p_name],
                     "Loss": list(ce.outputs["Y"]),
                     "Loss@GRAD": list(ce_twin[1].inputs.get(
                         "Y@GRAD", [EMPTY_VAR_NAME]))}
            g_outs = {"Logits@GRAD": [_grad_out(sm_twin[1], "X@GRAD")]}
            gfused = _new_op(None if dry_run else block, "softmax_with_cross_entropy_grad",
                             g_ins, g_outs, _grad_attrs(fused))
            first_twin = min(t[0] for t in twins)
            replacements[first_twin] = gfused
            removals |= {t[0] for t in twins} - set(replacements)
        op_idxs = sorted({i, j} | {t[0] for t in twins})
        rewrite = FusionRewrite(
            "softmax_xent", "softmax_with_cross_entropy", block.idx,
            op_idxs, vars=(op.inputs["X"][0], label), predicted=predicted,
            note="logsumexp form: loss differs from the eps-guarded "
                 "unfused pair by ~1e-6 relative (documented)",
            inserted=len(replacements))
        match = {"replacements": replacements, "removals": removals,
                 "rewrite": rewrite}
        if dry_run:
            report.record(rewrite)
            continue
        return match
    return None


# ---------------------------------------------------------------------------
# family: conv2d + batch_norm + activation  (fuse_bn_act_ops)
# ---------------------------------------------------------------------------

def _find_conv_bn_act(view, report, dry_run=False):
    """conv2d → batch_norm → (activation) ⇒ ``fused_conv_bn_act``.

    The biggest remaining kernel gap (ResNet-50 MFU 0.250 vs XLA's own
    0.381 accounting): the BN normalize/affine and the relu each pay a
    full HBM round-trip of the conv output, plus the framework op
    boundaries keep XLA from fusing training-mode BN stats back into
    one sweep.  The fused op keeps the conv on XLA's MXU schedule and
    runs the whole epilogue in one pass (Pallas where eligible —
    ops/pallas/conv_bn_act.py).  Gated by predicted HBM savings times
    the autotune calibration factor for the site's signature."""
    block = view.block
    for i, op in enumerate(block.ops):
        if op.type != "conv2d" or _is_grad_op(op):
            continue
        conv_out = op.outputs["Output"][0]
        cv = view.var(conv_out)
        conv_dtype = str(cv.dtype) if cv is not None else "float32"
        nxt = view.sole_fwd_consumer(conv_out)
        # AMP cast-sandwich: the bf16 rewrite inserts conv -> cast(f32)
        # -> batch_norm -> cast(compute dtype) -> act.  The fused op IS
        # that sandwich (f32 stats/normalize, output cast to the conv
        # dtype), so absorb the cast pair into the match.
        cast_in = None
        if nxt is not None and nxt[1].type == "cast" \
                and str(nxt[1].attrs.get("out_dtype")) == "float32" \
                and conv_dtype != "float32":
            cast_in = nxt[1]
            nxt = view.sole_fwd_consumer(cast_in.outputs["Out"][0])
        if nxt is None or nxt[1].type != "batch_norm":
            continue
        bn = nxt[1]
        bn_x = cast_in.outputs["Out"][0] if cast_in is not None \
            else conv_out
        if bn.inputs.get("X", [None])[0] != bn_x:
            continue
        conv_fmt = op.attrs.get("data_format", "NCHW")
        if conv_fmt == "AnyLayout":
            conv_fmt = "NCHW"
        bn_fmt = bn.attrs.get("data_layout", "NCHW")
        if conv_fmt != bn_fmt:
            continue
        scale = bn.inputs.get("Scale", [None])[0]
        bias = bn.inputs.get("Bias", [None])[0]
        mean = bn.inputs.get("Mean", [None])[0]
        var = bn.inputs.get("Variance", [None])[0]
        if None in (scale, bias, mean, var):
            continue
        y = bn.outputs["Y"][0]
        cast_out = None
        nxt2 = view.sole_fwd_consumer(y)
        if cast_in is not None and nxt2 is not None \
                and nxt2[1].type == "cast" \
                and str(nxt2[1].attrs.get("out_dtype")) == conv_dtype:
            cast_out = nxt2[1]
            nxt2 = view.sole_fwd_consumer(cast_out.outputs["Out"][0])
        if (cast_in is None) != (cast_out is None):
            continue  # half a sandwich — refuse rather than mis-type
        act_op = None
        if nxt2 is not None and nxt2[1].type in _ACT_TYPES \
                and not _is_grad_op(nxt2[1]):
            act_op = nxt2[1]
        group = [op] \
            + ([cast_in] if cast_in is not None else []) \
            + [bn] \
            + ([cast_out] if cast_out is not None else []) \
            + ([act_op] if act_op is not None else [])
        if act_op is not None:
            out_final = act_op.outputs["Out"][0]
        elif cast_out is not None:
            out_final = cast_out.outputs["Out"][0]
        else:
            out_final = y

        # grad twins (all-or-nothing; empty for inference programs)
        twins = []
        bad = False
        for o in group:
            t = view.twin(o, o.type + "_grad")
            if t is False:
                bad = True
                break
            if t is not None:
                twins.append((o, t))
        if bad or (twins and len(twins) != len(group)):
            continue
        all_ops = group + [t[1] for _, t in twins]
        # removed intermediates: conv out, the AMP cast temps, bn Y
        # (when anything follows it), and the saved batch stats
        # (consumed only by batch_norm_grad, which the fused grad's vjp
        # recompute replaces)
        removed = [conv_out]
        if cast_in is not None:
            removed.append(cast_in.outputs["Out"][0])
        if cast_out is not None or act_op is not None:
            removed.append(y)
        if cast_out is not None and act_op is not None:
            removed.append(cast_out.outputs["Out"][0])
        removed += [n for s in ("SavedMean", "SavedVariance")
                    for n in bn.outputs.get(s, []) if n]
        if not all(view.unconsumed(n, all_ops) for n in removed):
            continue
        conv_twin = next((t for o, t in twins if o is op), None)
        bn_twin = next((t for o, t in twins if o is bn), None)
        act_twin = next((t for o, t in twins if o is act_op), None)
        cout_twin = next((t for o, t in twins if o is cast_out), None)
        cin_twin = next((t for o, t in twins if o is cast_in), None)
        if twins:
            internal_grads = [_grad_out(bn_twin[1], "X@GRAD")]
            for tw in (act_twin, cout_twin, cin_twin):
                if tw is not None:
                    internal_grads.append(_grad_out(tw[1], "X@GRAD"))
            if not all(n == EMPTY_VAR_NAME or view.unconsumed(n, all_ops)
                       for n in internal_grads):
                continue

        # ---- cost gate: predicted HBM savings x autotune calibration
        # (the measure-and-learn loop: silicon re-weighs the constant) --
        out_bytes = _var_bytes(view, conv_out)
        n_removed = len(group) - 1
        act_name = act_op.type if act_op is not None else "identity"
        ov = view.var(conv_out)
        factor, sig, calibrated = _calibration(
            "conv_bn_act",
            shape=tuple(ov.shape) if ov is not None and ov.shape else (),
            dtype=str(ov.dtype) if ov is not None else "float32",
            act=act_name)
        threshold = conv_bn_min_bytes()
        if out_bytes * factor < threshold:
            report.skip(
                "conv_bn_act", i, op.type,
                "cost model: fused epilogue saves ~%d B of HBM traffic "
                "per removed op, below the %d B gate (calibration x%.2f"
                "%s)" % (
                    int(out_bytes * factor), threshold, factor,
                    "" if calibrated else
                    " — uncalibrated: no autotune cache entry for %r "
                    "yet; a silicon sweep (paddle_tpu.autotune.sweep) "
                    "re-decides this gate" % sig),
                key=op.attrs.get("__op_id__"))
            continue

        predicted = {
            "hbm_bytes_saved": 2 * n_removed * out_bytes,
            "ops_removed": n_removed,
            "calibration": factor,
        }
        ins = {"Input": list(op.inputs["Input"]),
               "Filter": list(op.inputs["Filter"]),
               "Scale": [scale], "Bias": [bias],
               "Mean": [mean], "Variance": [var]}
        outs = {"Out": [out_final],
                "MeanOut": list(bn.outputs.get("MeanOut", [])),
                "VarianceOut": list(bn.outputs.get("VarianceOut", []))}
        fattrs = {k: v for k, v in op.attrs.items()
                  if not k.startswith("__") and k != "op_namescope"}
        for k in ("epsilon", "momentum", "is_test", "use_global_stats",
                  "data_layout"):
            if k in bn.attrs:
                fattrs[k] = bn.attrs[k]
        if act_op is not None:
            fattrs.update({k: v for k, v in act_op.attrs.items()
                           if not k.startswith("__")
                           and k != "op_namescope"})
        fattrs["act_type"] = act_name if act_op is not None else ""
        anchor = act_op if act_op is not None else (
            cast_out if cast_out is not None else bn)
        fused = _new_op(None if dry_run else block, "fused_conv_bn_act",
                        ins, outs, fattrs)
        replacements = {view.idx_of(anchor): fused}
        removals = {view.idx_of(o) for o in group} - set(replacements)
        if twins:
            g_ins = dict(ins)
            g_ins["Out"] = [out_final]
            if act_twin is not None:
                last_twin, og_slot = act_twin, "Out@GRAD"
            elif cout_twin is not None:
                last_twin, og_slot = cout_twin, "Out@GRAD"
            else:
                last_twin, og_slot = bn_twin, "Y@GRAD"
            g_ins["Out@GRAD"] = list(last_twin[1].inputs.get(
                og_slot, [EMPTY_VAR_NAME]))
            g_outs = {
                "Input@GRAD": [_grad_out(conv_twin[1], "Input@GRAD")],
                "Filter@GRAD": [_grad_out(conv_twin[1], "Filter@GRAD")],
                "Scale@GRAD": [_grad_out(bn_twin[1], "Scale@GRAD")],
                "Bias@GRAD": [_grad_out(bn_twin[1], "Bias@GRAD")],
            }
            gfused = _new_op(None if dry_run else block,
                             "fused_conv_bn_act_grad", g_ins, g_outs,
                             _grad_attrs(fused))
            first_twin = min(t[0] for _, t in twins)
            replacements[first_twin] = gfused
            removals |= {t[0] for _, t in twins} - set(replacements)
        op_idxs = sorted({view.idx_of(o) for o in group}
                         | {t[0] for _, t in twins})
        rewrite = FusionRewrite(
            "conv_bn_act", "fused_conv_bn_act", block.idx, op_idxs,
            vars=(op.inputs["Input"][0], op.inputs["Filter"][0], scale,
                  bias),
            predicted=predicted,
            note="%s epilogue%s%s; f32 XLA-composite path bit-exact, "
                 "Pallas path ~1e-6; AMP sandwich lets XLA reassociate "
                 "the BN scale/bias grad reductions (~1e-4 rel, "
                 "documented)" % (
                     act_name,
                     " +AMP cast sandwich" if cast_in is not None else "",
                     "" if calibrated else " (uncalibrated gate)"),
            inserted=len(replacements))
        match = {"replacements": replacements, "removals": removals,
                 "rewrite": rewrite}
        if dry_run:
            report.record(rewrite)
            continue
        return match
    return None


# ---------------------------------------------------------------------------
# family: bucketed gradient allreduce  (fuse_all_reduce_ops)
# ---------------------------------------------------------------------------

def _find_allreduce(view, report, dry_run=False):
    from .defuse import resolve_sub_block, sub_block_reads_recursive

    block = view.block
    groups = {}
    for i, op in enumerate(block.ops):
        if op.type != "c_allreduce_sum":
            continue
        x = op.inputs.get("X", [None])
        o = op.outputs.get("Out", [None])
        if len(x) != 1 or len(o) != 1 or x[0] != o[0] or x[0] is None:
            continue  # only the in-place grad-allreduce shape buckets
        nbytes = _var_bytes(view, x[0])
        if not nbytes:
            continue
        key = (op.attrs.get("ring_id"), op.attrs.get("pre_scale"),
               str(view.var(x[0]).dtype))
        groups.setdefault(key, []).append((i, op, nbytes))

    cap = int(allreduce_bucket_mb(block.program) * (1 << 20))
    # quantized-collective engagement: the planner's _quant_buckets mark
    # (or the env override) names the per-bucket byte threshold; None =
    # quant off for this program → plain bf16 coalescing only
    from ..quant.blockwise import quant_block as _quant_block
    from ..quant.collective import (quant_min_bytes as _quant_min,
                                    quantized_wire_bytes)

    qmin = _quant_min(block.program)
    qblock = _quant_block()
    matches = []
    for key, members in sorted(groups.items(),
                               key=lambda kv: kv[1][0][0]):
        # split into size-capped buckets, in program order
        buckets = []
        cur, cur_bytes = [], 0
        for i, op, nbytes in members:
            if cur and cur_bytes + nbytes > cap:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append((i, op, nbytes))
            cur_bytes += nbytes
        if cur:
            buckets.append(cur)
        for bucket in buckets:
            # a quantizable bucket engages at ANY member count (a lone
            # big grad still wins the byte cut); without quant a
            # single-member bucket has nothing to coalesce
            quantizable = (qmin is not None
                           and key[2] in ("float32", "bfloat16")
                           and sum(b for _, _, b in bucket) >= qmin)
            if len(bucket) < 2 and not quantizable:
                continue  # nothing to coalesce; no advisory noise
            flush_idx = bucket[-1][0]
            member_ids = {id(op) for _, op, _ in bucket}
            # safety: coalescing delays each member's reduction to the
            # flush site — no op in between may read or write the grad
            # (the optimizer consumes it later; a clip/scale in between
            # would read the un-reduced value under shard_map)
            safe = []
            for i, op, nbytes in bucket:
                g = op.inputs["X"][0]
                ok = True
                for j in range(i + 1, flush_idx + 1):
                    other = block.ops[j]
                    if id(other) in member_ids:
                        continue
                    if g in other.input_arg_names \
                            or g in other.output_arg_names:
                        ok = False
                        break
                    # closure reads never show on input slots: a
                    # while/conditional body capturing the grad in the
                    # window would see the un-reduced local value
                    sub = resolve_sub_block(view.program, other,
                                            host_block_idx=block.idx)
                    if sub is not None and g in sub_block_reads_recursive(
                            view.program, sub):
                        ok = False
                        break
                if ok:
                    safe.append((i, op, nbytes))
                else:
                    report.skip(
                        "allreduce", i, op.type,
                        "grad %r is read/written between its allreduce "
                        "and the bucket flush site — stays unfused" % g,
                        key=op.attrs.get("__op_id__"))
            total = sum(b for _, _, b in safe)
            quant = (qmin is not None
                     and key[2] in ("float32", "bfloat16")
                     and total >= qmin)
            if len(safe) < (1 if quant else 2):
                continue
            names = [op.inputs["X"][0] for _, op, _ in safe]
            attrs = {"ring_id": key[0], "op_role": "backward"}
            if key[1]:
                attrs["pre_scale"] = key[1]
            if quant:
                attrs["quant_block"] = qblock
            fused_type = "c_allreduce_quant" if quant \
                else "c_fused_allreduce_sum"
            fused = _new_op(None if dry_run else block, fused_type,
                            {"X": list(names)}, {"Out": list(names)},
                            attrs)
            if quant:
                numel = total // max(dtype_bytes(key[2]), 1)
                wire, dense = quantized_wire_bytes(
                    numel, 2, block=qblock, dtype_bytes=dtype_bytes(key[2]))
                predicted = {
                    "collectives_removed": len(safe) - 1,
                    "ici_bytes_saved": dense - wire,
                    "quant_block": qblock,
                    "bucket_mb_cap": allreduce_bucket_mb(block.program),
                }
                note = ("ring %r; int8 wire %d -> %d bytes, "
                        "%d launches -> 1"
                        % (key[0], dense, wire, len(safe)))
            else:
                predicted = {
                    "collectives_removed": len(safe) - 1,
                    "ici_bytes_unchanged": total,
                    "bucket_mb_cap": allreduce_bucket_mb(block.program),
                }
                note = ("ring %r; ICI volume unchanged, %d launches -> 1"
                        % (key[0], len(safe)))
            rewrite = FusionRewrite(
                "allreduce", fused_type, block.idx,
                [i for i, _, _ in safe], vars=tuple(names),
                predicted=predicted, note=note)
            matches.append({
                "replacements": {safe[-1][0]: fused},
                "removals": {i for i, _, _ in safe[:-1]},
                "rewrite": rewrite,
            })
    if dry_run:
        for m in matches:
            report.record(m["rewrite"])
        return None
    return matches[0] if matches else None


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------

_FAMILIES = (
    ("attention", "fuse_attention", _find_attention),
    ("conv_bn_act", "fuse_conv_bn_act", _find_conv_bn_act),
    ("softmax_xent", "fuse_softmax_xent", _find_softmax_xent),
    ("dropout_add_ln", "fuse_elewise", _find_dropout_add_ln),
    ("bias_act", "fuse_elewise", _find_bias_act),
    ("allreduce", "fuse_allreduce", _find_allreduce),
)

_MAX_REWRITES = 10000  # runaway-loop backstop
_FUSION_CACHE_CAP = 16  # resolved-clone cache entries per program


def _run_family(view, find, report):
    # re-scans after an applied rewrite re-encounter still-gated sites;
    # FusionReport.skip dedupes them by anchor-op identity
    applied = 0
    while applied < _MAX_REWRITES:
        match = find(view, report)
        if match is None:
            return applied
        _replace_ops(view.block, match["replacements"],
                     match["removals"])
        report.record(match["rewrite"])
        view.refresh()
        applied += 1
    return applied


def apply_fusion_passes(program, config=None, targets=(), verify=None,
                        baseline=None):
    """Run the fusion pipeline over ``program`` IN PLACE; returns the
    :class:`FusionReport`.  Each family is bracketed by the verifier
    when pass verification is enabled (on in tests) so a bad rewrite is
    named instead of surfacing as an opaque trace error.

    The bracket is BASELINE-AWARE: only errors a fusion pass *introduces*
    fail it.  Programs can legitimately arrive with pre-existing
    ERROR-severity metadata drift (e.g. the AMP bf16 rewrite flips var
    dtypes without re-running inference on every recorded shape) that
    the executor tolerates — a rewrite pass must not be blamed for it."""
    config = config or FusionConfig.default()
    report = FusionReport(config)
    if not config.enabled:
        return report
    if verify is None:
        from .verifier import pass_verification_enabled

        verify = pass_verification_enabled()
    view = _GlobalView(program, targets)

    if verify and baseline is None:
        baseline = _error_signatures(program, view.targets)
    for family, flag, find in _FAMILIES:
        if not getattr(config, flag):
            continue
        n = _run_family(view, find, report)
        if n and verify:
            _assert_no_new_errors(program, view.targets, baseline,
                                  "after fuse_%s_pass" % family)
    return report


# advisory-only checks skipped inside the pass brackets: the bracket
# gates on ERROR findings only, and fusible-pattern-not-fused re-runs
# every matcher (O(families x ops) per verify) just to produce INFO
# lines the bracket would filter out anyway
_BRACKET_EXCLUDE = ("fusible-pattern-not-fused", "unreferenced-op",
                    "resilience-finite-guard",
                    "executor-host-sync-in-loop", "sync-in-hot-loop",
                    "quantizable-bucket-not-quantized",
                    "collective-crosses-slow-tier",
                    "overlap-opportunity-unexploited")


# the in-flight depth the bracket's race checks assume: a fusion
# rewrite must be safe for the async serving/training paths whatever
# depth the caller later picks, so the bracket models the overlapped
# case (K=2) even for a program that will run sequentially —
# baseline-aware diffing means pre-existing races are never blamed on
# the pass, only INTRODUCED ones fail it
_BRACKET_MAX_IN_FLIGHT = 2


def _finding_signature(d):
    """Baseline-diff key for one ERROR finding.  Op indices are
    deliberately excluded so removing ops ahead of a pre-existing
    finding does not make it look new; race findings also drop the
    message, which names the writing op's TYPE — rewriting ``batch_norm``
    into ``fused_conv_bn_act`` must not make a pre-existing race look
    introduced."""
    from .concurrency import RACE_CHECK_IDS

    if d.check in RACE_CHECK_IDS:
        return (d.check, d.var_names)
    return (d.check, d.message, d.var_names)


def _error_signatures(program, targets):
    """Signatures of every ERROR finding (see
    :func:`_finding_signature`)."""
    from .diagnostics import Severity
    from .verifier import verify_program

    return {
        _finding_signature(d)
        for d in verify_program(program, targets=list(targets),
                                exclude=_BRACKET_EXCLUDE,
                                max_in_flight=_BRACKET_MAX_IN_FLIGHT)
        if d.severity >= Severity.ERROR
    }


def _assert_no_new_errors(program, targets, baseline, context):
    from .diagnostics import Severity, format_diagnostics
    from .verifier import VerifyError, verify_program

    diags = verify_program(program, targets=list(targets),
                           exclude=_BRACKET_EXCLUDE,
                           max_in_flight=_BRACKET_MAX_IN_FLIGHT)
    new = [d for d in diags
           if d.severity >= Severity.ERROR
           and _finding_signature(d) not in baseline]
    if new:
        raise VerifyError(
            format_diagnostics(
                new, header="program failed verification (%s):" % context),
            diagnostics=new)


def scan_fusible_patterns(program, config=None, targets=()):
    """Dry-run the matchers without mutating the program — the engine
    behind the ``fusible-pattern-not-fused`` advisory check.  Returns a
    :class:`FusionReport` whose ``applied`` lists patterns that WOULD
    fuse and ``skipped`` the matched-but-gated-out ones."""
    config = config or FusionConfig.default()
    report = FusionReport(config)
    view = _GlobalView(program, targets)
    for family, flag, find in _FAMILIES:
        if not getattr(config, flag):
            continue
        find(view, report, dry_run=True)
    return report


# registered pass-pipeline entry points (analysis.register_pass idiom);
# each runs ONE family so a PassBuilder can compose them individually
def _make_pass(family, flag, find):
    def _pass(program, scope=None, targets=None):
        config = FusionConfig.default()
        if not config.enabled or not getattr(config, flag):
            return program
        report = FusionReport(config)
        view = _GlobalView(program, targets or ())
        _run_family(view, find, report)
        return program

    _pass.__name__ = "fuse_%s_pass" % family
    return _pass


def _register_passes():
    from ..analysis import register_pass

    for family, flag, find in _FAMILIES:
        register_pass("fuse_%s_pass" % family)(
            _make_pass(family, flag, find))


_register_passes()


def _run_hierarchy_pass(clone, targets, baseline=None):
    """Run the hierarchical-collective decomposition on the resolved
    clone after the fusion pipeline (it decomposes the bucketed
    collectives fusion just emitted) and BEFORE the overlap scheduler
    (the remaining flat buckets can still split into start/wait pairs;
    the hierarchical hops themselves opt out of overlap).  Bracketed by
    the verifier like a fusion family; returns whether any bucket
    decomposed — the resolve cache must keep the clone for a
    hierarchy-only rewrite."""
    from .hierarchy import apply_hierarchy_pass, hierarchy_enabled

    if not hierarchy_enabled(clone):
        clone._hierarchy_report = None
        return False
    from .verifier import pass_verification_enabled

    verify = pass_verification_enabled()
    if verify and baseline is None:
        baseline = _error_signatures(clone, set(targets))
    applied = apply_hierarchy_pass(clone, targets=targets)
    if applied and verify:
        _assert_no_new_errors(clone, set(targets), baseline,
                              "after hierarchy_pass")
    return applied


def _run_overlap_pass(clone, targets, baseline=None):
    """Run the overlap scheduler on the resolved clone after the fusion
    pipeline (it splits the bucketed collectives fusion just emitted),
    bracketed by the verifier exactly like a fusion family.  Returns
    whether any bucket was actually split — the resolve cache must keep
    the clone even when no FUSION family fired, or the overlap-only
    rewrite would be thrown away.

    ``baseline`` is the pre-fusion error-signature set the fusion
    pipeline already computed; reusing it keeps the bracket one verify
    per resolve instead of two (each family that fired already asserted
    it introduced nothing over the same baseline)."""
    from .overlap import apply_overlap_pass, overlap_enabled

    if not overlap_enabled(clone):
        return False
    from .verifier import pass_verification_enabled

    verify = pass_verification_enabled()
    if verify and baseline is None:
        baseline = _error_signatures(clone, set(targets))
    ov = apply_overlap_pass(clone, targets=targets)
    if ov.applied and verify:
        _assert_no_new_errors(clone, set(targets), baseline,
                              "after overlap_schedule_pass")
    return bool(ov.applied)


# ---------------------------------------------------------------------------
# executor entry: fused-clone resolution + caching
# ---------------------------------------------------------------------------

def resolve_fused_program(program, config=None, targets=()):
    """Resolve the fusion-rewritten twin of ``program`` for execution.

    Returns ``(program_to_run, FusionReport)``.  The rewritten program
    is a CLONE (the user's program object is never mutated — fusion-off
    runs stay bit-exact with the pre-fusion paths), cached on the
    original keyed by (config signature, program version, fetch set), so
    the executor's jit cache — which keys on the resolved program's
    identity/version plus the fusion signature — compiles each fusion
    config exactly once.  Cloning preserves ``__op_id__``s, so the
    deterministic RNG streams of UNtouched ops (dropout elsewhere in the
    model) are identical with fusion on and off.
    """
    config = config or FusionConfig.default()
    if getattr(program, "_fusion_applied", False):
        return program, getattr(program, "_fusion_report", None) \
            or FusionReport(config)
    if not config.enabled:
        report = FusionReport(config)
        return program, report
    from ..observability import runtime as _obs

    tkey = tuple(sorted({getattr(t, "name", t) for t in (targets or ())}))
    key = (config.signature(program), program._version, tkey)
    cache = program.__dict__.setdefault("_fusion_cache", {})
    hit = cache.get(key)
    if hit is not None:
        _obs.record_fusion_resolve(True)
        fused, report = hit
        return (fused if fused is not None else program), report
    _obs.record_fusion_resolve(False)
    # drop entries of stale versions so a mutated-every-step program
    # cannot leak clones
    for k in [k for k in cache if k[1] != program._version]:
        del cache[k]
    # and cap distinct (config, fetch-set) entries: a serving loop
    # fetching per-request variable subsets must not accumulate
    # unbounded program clones (FIFO — dicts preserve insertion order)
    while len(cache) >= _FUSION_CACHE_CAP:
        del cache[next(iter(cache))]
    clone = program.clone()
    for mark in _PROGRAM_MARKS:
        if hasattr(program, mark):
            setattr(clone, mark, getattr(program, mark))
    _copy_var_marks(program, clone)
    clone._fusion_applied = True
    from .verifier import pass_verification_enabled

    baseline = None
    if pass_verification_enabled():
        # one pre-rewrite verify shared by the fusion families AND the
        # overlap pass bracket (each asserts against the same baseline)
        baseline = _error_signatures(clone, set(tkey))
    report = apply_fusion_passes(clone, config, targets=tkey,
                                 baseline=baseline)
    hier_applied = _run_hierarchy_pass(clone, tkey, baseline=baseline)
    overlap_applied = _run_overlap_pass(clone, tkey, baseline=baseline)
    if not report.applied and not overlap_applied and not hier_applied:
        cache[key] = (None, report)
        return program, report
    clone._fusion_sig = config.signature(program)
    clone._fusion_report = report
    cache[key] = (clone, report)
    try:
        from ..observability import journal as _journal

        _journal.emit(
            "fusion-applied",
            applied={name: count for name, count
                     in sorted(report.applied.items())}
            if isinstance(report.applied, dict)
            else list(report.applied),
            signature=config.signature(program))
    except Exception:  # noqa: BLE001 - telemetry never breaks resolve
        pass
    return clone, report
