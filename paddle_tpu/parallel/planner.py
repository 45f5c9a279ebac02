"""Auto-parallelism planner: search the placement/sharding space the
static analyzer can already price.

The reference stack makes distribution a USER decision: pick
``DistributeTranspiler`` vs fleet ``DistributedStrategy``, pick DP vs
pipeline vs MoE vs ulysses, pick the allreduce bucket size — then hope.
Following "Synthesizing Optimal Parallelism Placement and Reduction
Strategies on Hierarchical Systems" (arXiv:2110.10548, PAPERS.md), this
module closes the loop with the ingredients PR 1-6 built:

* **candidate enumeration** — data-parallel (with bucketed-allreduce
  launch counts and optional ZeRO-1 optimizer-state sharding seeded
  through the interp's sharding lattice), pipeline stage splits (cut
  points searched over layer boundaries by a bounded branch-and-bound
  over per-layer fwd+bwd FLOP loads, reusing ``transpile_pipeline``'s
  stage-assignment rules), and MoE / ulysses replication where the
  program already carries those collectives;
* **pricing** — every candidate's per-worker programs go through the
  PR-3 cost model (:func:`~paddle_tpu.static_analysis.cost.price_plan`)
  against a :class:`ClusterSpec`, multiplied by the PR-6 autotune
  ``calibration_factors()`` so estimates track measured silicon;
* **pruning** — candidates whose peak HBM exceeds the budget
  (``PADDLE_TPU_HBM_BUDGET`` or ``ClusterSpec.hbm_gb``) are marked
  infeasible; when NOTHING fits, the planner degrades to the
  least-memory plan instead of crashing;
* **proof** — the winner's collective schedule must pass the PR-3
  three-layer deadlock-freedom proof
  (:mod:`~paddle_tpu.static_analysis.distributed`) before its worker
  programs are returned; a candidate that fails the proof is rejected
  with the diagnostic and the next-cheapest takes its place;
* **determinism** — identical (program, ClusterSpec) inputs always
  yield the byte-identical plan: enumeration order is fixed, every
  sort carries the candidate's ``plan_key()`` as tie-break, and no
  wall-clock, RNG, or set-iteration order reaches a decision.

Entry point: ``parallel.auto_transpile(program, cluster_spec)`` →
:class:`PlanResult` (chosen plan + per-worker programs + the full
candidate table).  Front-ends: fleet ``DistributedStrategy.auto=True``
and ``DistributeTranspilerConfig.mode="auto"`` route here; the CLI
``python -m paddle_tpu.tools.analyze_program --plan cluster.json``
prints the candidate table without executing anything.
"""

import json
import math
import os

from ..static_analysis.cost import (dtype_bytes, estimate_cost,
                                    hbm_budget, price_plan)
from ..static_analysis.distributed import (check_schedule_consistency,
                                           extract_collective_schedule)
from ..static_analysis.interp import (DATA_AXIS, Sharding,
                                      interpret_program)

__all__ = ["ClusterSpec", "PlanCandidate", "PricedCandidate",
           "PlanResult", "auto_transpile", "apply_plan",
           "enumerate_candidates", "price_worker_set",
           "resolve_cluster_spec", "select_dp_standin"]

_MB = 1024 * 1024

# comm tags whose presence makes the moe / ulysses replication
# candidates applicable — the emitters stamp their all_to_all ops with
# these (the program already expresses that parallelism; the planner's
# job is then to price it against the alternatives)
_MOE_COMM_TAGS = ("moe_dispatch", "moe_combine")
_ULYSSES_COMM_TAGS = ("ulysses_to_heads", "ulysses_to_seq")


class ClusterSpec:
    """The hierarchical system the planner places onto: chip count plus
    the hardware numbers the cost model prices against.  Defaults are a
    generic contemporary TPU chip; load deployment truth from JSON::

        {"chips": 8, "peak_tflops": 275, "hbm_gb": 16,
         "hbm_gbps": 1200, "ici_gbps": 100, "launch_us": 5,
         "topology": "ring"}

    A MULTI-SLICE deployment adds the topology tree — chips within a
    slice over ICI, slices (within a pod) over DCN, pods over the WAN
    tier — each tier with its own bandwidth/latency::

        {"chips": 8, "slices": 2, "dcn_gbps": 25, "dcn_launch_us": 50}

    The flat form is the ``slices=1, pods=1`` degenerate tree, so every
    existing spec (bare chip counts, old JSON files) coerces unchanged
    and — because :meth:`to_dict` only emits topology fields when a
    topology is actually declared — serializes byte-identically to
    before the tree existed."""

    __slots__ = ("chips", "peak_tflops", "hbm_gb", "hbm_gbps",
                 "ici_gbps", "launch_us", "topology",
                 "slices", "dcn_gbps", "dcn_launch_us",
                 "pods", "pod_gbps", "pod_launch_us")

    #: topology-tree fields: omitted from to_dict()/repr() on flat specs
    _TOPOLOGY_FIELDS = ("slices", "dcn_gbps", "dcn_launch_us",
                        "pods", "pod_gbps", "pod_launch_us")

    def __init__(self, chips=1, peak_tflops=100.0, hbm_gb=16.0,
                 hbm_gbps=1200.0, ici_gbps=100.0, launch_us=5.0,
                 topology="ring", slices=1, dcn_gbps=25.0,
                 dcn_launch_us=50.0, pods=1, pod_gbps=5.0,
                 pod_launch_us=200.0):
        self.chips = max(1, int(chips))
        self.peak_tflops = float(peak_tflops)
        self.hbm_gb = float(hbm_gb)
        self.hbm_gbps = float(hbm_gbps)
        self.ici_gbps = float(ici_gbps)
        self.launch_us = float(launch_us)
        self.topology = str(topology)
        self.slices = max(1, int(slices))
        self.dcn_gbps = float(dcn_gbps)
        self.dcn_launch_us = float(dcn_launch_us)
        self.pods = max(1, int(pods))
        self.pod_gbps = float(pod_gbps)
        self.pod_launch_us = float(pod_launch_us)
        if self.has_topology and self.chips % (self.slices * self.pods):
            raise ValueError(
                "asymmetric topology: chips=%d is not divisible by "
                "slices×pods (%d×%d) — every slice must hold the same "
                "chip count" % (self.chips, self.slices, self.pods))

    @property
    def hbm_bytes(self):
        return int(self.hbm_gb * 1024 ** 3)

    # ---- the topology tree ----

    @property
    def has_topology(self):
        """True when the spec declares more than one ICI domain."""
        return self.slices > 1 or self.pods > 1

    @property
    def chips_per_slice(self):
        """Chips sharing one fast (ICI) domain."""
        return self.chips // (self.slices * self.pods)

    def tier_for(self, participants):
        """The slowest wire tier a ring of ``participants`` co-located
        ranks crosses: ``"ici"`` inside one slice, ``"dcn"`` across
        slices, ``"pod"`` across pods.  Flat specs answer ``"ici"`` for
        any size."""
        if not self.has_topology or participants <= self.chips_per_slice:
            return "ici"
        if self.pods > 1 and participants > self.chips // self.pods:
            return "pod"
        return "dcn"

    def tier_wire(self):
        """``{tier: (gbps, launch_us)}`` for the tiers this spec
        declares, fastest first."""
        out = {"ici": (self.ici_gbps, self.launch_us)}
        if self.slices > 1:
            out["dcn"] = (self.dcn_gbps, self.dcn_launch_us)
        if self.pods > 1:
            out["pod"] = (self.pod_gbps, self.pod_launch_us)
        return out

    @classmethod
    def coerce(cls, spec):
        """ClusterSpec | dict | bare chip count | JSON file path |
        JSON string (object or bare number) → spec."""
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, str):
            if os.path.exists(spec):
                with open(spec) as f:
                    spec = json.load(f)
            else:
                spec = json.loads(spec)
        if isinstance(spec, (int, float)) and not isinstance(spec, bool):
            return cls(chips=int(spec))
        if not isinstance(spec, dict):
            raise TypeError("cannot build a ClusterSpec from %r" % (spec,))
        known = {k: spec[k] for k in cls.__slots__ if k in spec}
        unknown = sorted(set(spec) - set(cls.__slots__))
        if unknown:
            raise ValueError("unknown ClusterSpec field(s) %s (known: %s)"
                             % (unknown, list(cls.__slots__)))
        return cls(**known)

    def to_dict(self):
        flat = {k: getattr(self, k) for k in self.__slots__
                if k not in self._TOPOLOGY_FIELDS}
        if self.has_topology:
            flat.update({k: getattr(self, k)
                         for k in self._TOPOLOGY_FIELDS})
        return flat

    def __repr__(self):
        return "ClusterSpec(%s)" % ", ".join(
            "%s=%r" % (k, v) for k, v in self.to_dict().items())


def resolve_cluster_spec(chips=None):
    """The deployment's :class:`ClusterSpec`:
    ``PADDLE_TPU_CLUSTER_SPEC`` (a JSON file path or inline JSON) when
    set, defaults otherwise — with ``chips`` (the ACTUAL worker count
    the fleet/transpiler front-ends know) overriding the spec's chip
    count, because the planner must place onto the cluster that exists,
    not the one the config file remembers."""
    raw = os.environ.get("PADDLE_TPU_CLUSTER_SPEC", "").strip()
    spec = ClusterSpec.coerce(raw) if raw else ClusterSpec()
    if chips:
        spec.chips = max(1, int(chips))
        if spec.has_topology and spec.chips % (spec.slices * spec.pods):
            # the fleet's actual world doesn't fill the configured tree
            # symmetrically — degrade to a flat (single-tier) spec
            # rather than price a topology that doesn't exist
            spec.slices = spec.pods = 1
    return spec


def select_dp_standin(result):
    """The dp-family candidate that stands in when the winner cannot be
    expressed (in-place apply) or executed (the bench's measured arm)
    in one worker's program: the cheapest FEASIBLE non-divergent
    dp/single candidate, else the least-memory one (plan_key
    tie-break) — never a cheaper-but-over-budget dp whose OOM the
    candidate table itself predicts.  One policy, shared by
    :func:`apply_plan` and ``bench.py --child planner``.  Returns the
    :class:`PricedCandidate` or None."""
    dp_pool = [pc for pc in result.candidates
               if pc.candidate.kind in ("dp", "single")
               and pc.deadlock != "divergent"]
    for pc in dp_pool:  # result.candidates is ranked by step_ms
        if pc.feasible:
            return pc
    if dp_pool:
        return min(dp_pool,
                   key=lambda pc: (pc.price.peak_memory_bytes,
                                   pc.candidate.plan_key()))
    return None


def apply_plan(program, result, startup_program=None, rank=0):
    """Apply ``result``'s winning plan to ``program`` IN PLACE where
    one worker's program can express it (the dp family) — the shared
    tail of both ``auto`` front-ends (fleet ``DistributedStrategy.auto``
    and ``DistributeTranspilerConfig.mode="auto"``).

    Realizes every knob the plan was priced with: the GradAllReduce
    transpile at the plan's degree, the ZeRO-1 stamp
    (``program._shard_optimizer_state`` — the SPMD runner enables
    sharding when either this stamp or the BuildStrategy flag is set;
    clear the stamp to disable it), and the allreduce
    bucket cap as the ``program._allreduce_bucket_mb`` mark the fusion
    pass consults before the env var — scoped to THIS program, so an
    auto apply neither leaks into nor clobbers another program's
    ``PADDLE_TPU_ALLREDUCE_BUCKET_MB`` configuration.  A dp winner
    chosen FOR its bucket/zero1 numbers must not silently run without
    them.  The full :class:`PlanResult` lands on ``program._auto_plan``
    either way.

    A non-dp winner (a pipeline stage set) cannot be expressed by
    mutating one program — leaving the program untranspiled would make
    N workers train on disjoint shards with NO gradient sync, silently
    divergent.  So the in-place apply falls back to the cheapest
    dp-family candidate (warning that the cheaper plan lives in
    ``result.worker_programs`` for per-stage deployment).  Returns the
    applied :class:`PlanCandidate`."""
    import warnings

    program._auto_plan = result
    cand = result.plan.candidate
    if cand.kind not in ("dp", "single"):
        applied_pc = select_dp_standin(result)
        applied = applied_pc.candidate if applied_pc else None
        warnings.warn(
            "auto plan winner %r cannot be applied in place (one "
            "worker's program cannot express a %s plan) — applying %s "
            "instead; deploy result.worker_programs to run the cheaper "
            "plan" % (cand.describe(), cand.kind,
                      applied.describe() if applied else
                      "plain grad-allreduce DP"),
            stacklevel=2)
        cand = applied or PlanCandidate("dp", result.cluster.chips)
    program._auto_plan_applied = cand
    if cand.kind == "single":
        return cand
    from ..static_analysis.verifier import pass_verification_enabled
    from ..transpiler.collective import GradAllReduce

    # rewrite bracket (ISSUE 10): the transpile may not introduce an
    # in-flight race the input program didn't have — same contract the
    # fusion passes carry, baseline-aware so pre-existing races are
    # not blamed on the planner
    verify = pass_verification_enabled()
    race_baseline = None
    if verify:
        from ..static_analysis.concurrency import race_signatures

        race_baseline = race_signatures(program)
    GradAllReduce().transpile(program=program,
                              startup_program=startup_program,
                              rank=rank, nranks=cand.degree)
    if verify:
        from ..static_analysis.concurrency import assert_no_new_races

        assert_no_new_races(program, race_baseline,
                            "auto-plan apply (%s)" % cand.describe())
    program._shard_optimizer_state = cand.zero1
    if cand.bucket_mb:
        program._allreduce_bucket_mb = cand.bucket_mb
    if getattr(cand, "quant", False):
        # per-bucket realization of the quant axis: the fusion rewrite
        # consults this mark (quant.collective.quant_min_bytes) and only
        # quantizes buckets at or above the cluster's break-even size —
        # smaller (compute-bound) buckets keep the bf16 fused op
        program._quant_buckets = quant_bucket_mark(result.cluster,
                                                   cand.degree)
    from ..static_analysis.overlap import overlap_enabled
    if overlap_enabled():
        # the axis was searched: realize the verdict either way — a
        # winner priced WITHOUT overlap must not silently run with it
        # (the mark wins over the env default in overlap_enabled()).
        # Kill switch off → axis absent → no stamp, schedule untouched.
        program._overlap = bool(getattr(cand, "overlap", False))
    from ..static_analysis.hierarchy import hierarchy_enabled
    if getattr(result.cluster, "has_topology", False):
        # pin the topology the plan was priced with (the lint advisory
        # and FusionConfig.signature read this mark) and realize the
        # hier verdict either way when the axis was searched
        program._cluster_spec = result.cluster.to_dict()
        if hierarchy_enabled():
            program._hierarchy = (
                {"chips_per_slice": result.cluster.chips_per_slice}
                if getattr(cand, "hier", False) else False)
    return cand


class PlanCandidate:
    """One point of the placement/sharding search space."""

    __slots__ = ("kind", "degree", "stages", "dp_degree", "cuts",
                 "bucket_mb", "zero1", "microbatches", "quant",
                 "overlap", "hier")

    def __init__(self, kind, degree, stages=1, dp_degree=1, cuts=(),
                 bucket_mb=None, zero1=False, microbatches=1,
                 quant=False, overlap=False, hier=False):
        self.kind = kind            # single | dp | pipeline | moe | ulysses
        self.degree = int(degree)   # total chips the plan occupies
        self.stages = int(stages)
        self.dp_degree = int(dp_degree)
        self.cuts = tuple(cuts)
        self.bucket_mb = bucket_mb
        self.zero1 = bool(zero1)
        self.microbatches = int(microbatches)
        self.quant = bool(quant)    # int8 block-quantized grad exchange
        self.overlap = bool(overlap)  # start/wait split allreduce schedule
        self.hier = bool(hier)      # hierarchical RS/AR/AG decomposition

    def plan_key(self):
        """Deterministic identity/tie-break key.  ``hier=False`` and
        ``overlap=False`` sort first, so a tie (no slow-tier bytes
        actually saved / no wire hidden) resolves to the flat
        synchronous schedule.  ``quant`` stays the LAST element — the
        established ``plan_key()[:-1]`` idiom for "this plan modulo the
        quant axis" keeps working."""
        return (self.kind, self.degree, self.stages, self.dp_degree,
                self.bucket_mb if self.bucket_mb is not None else -1,
                self.zero1, self.cuts, self.hier, self.overlap,
                self.quant)

    def describe(self):
        if self.kind == "single":
            return "single-chip (no transpile)"
        if self.kind == "dp":
            s = "dp x%d" % self.degree
            if self.zero1:
                s += " +zero1"
            if self.hier:
                s += " +hier"
            if self.quant:
                s += " +int8"
            if self.overlap:
                s += " +overlap"
            if self.bucket_mb:
                s += " (allreduce bucket %dMB)" % self.bucket_mb
            return s
        if self.kind == "pipeline":
            s = "pipeline x%d stages" % self.stages
            if self.dp_degree > 1:
                s += " x dp %d" % self.dp_degree
            return s + " (M=%d, cuts: %s)" % (self.microbatches,
                                              ", ".join(self.cuts))
        return "%s x%d (replicated worker set)" % (self.kind, self.degree)

    def to_dict(self):
        return {
            "kind": self.kind, "degree": self.degree,
            "stages": self.stages, "dp_degree": self.dp_degree,
            "cuts": list(self.cuts), "bucket_mb": self.bucket_mb,
            "zero1": self.zero1, "microbatches": self.microbatches,
            "quant": self.quant, "overlap": self.overlap,
            "hier": self.hier,
            "describe": self.describe(),
        }

    def __repr__(self):
        return "PlanCandidate(%s)" % self.describe()


class PricedCandidate:
    """A candidate with its price, feasibility and (for the winner /
    rejected finalists) the deadlock verdict."""

    __slots__ = ("candidate", "price", "feasible", "budget", "status",
                 "deadlock", "chosen")

    def __init__(self, candidate, price, budget):
        self.candidate = candidate
        self.price = price
        self.budget = budget
        self.feasible = (budget is None
                         or price.peak_memory_bytes <= budget)
        self.status = ""
        self.deadlock = None    # None = not proven; "ok"; "divergent"
        self.chosen = False

    def to_dict(self, canonical=False):
        return {
            "candidate": self.candidate.to_dict(),
            "price": self.price.to_dict(canonical=canonical),
            "feasible": self.feasible,
            "hbm_budget": self.budget,
            "deadlock": self.deadlock,
            "chosen": self.chosen,
            "status": self.status,
        }


class PlanResult:
    """What :func:`auto_transpile` returns: the chosen plan, its
    emitted per-worker programs, and the whole priced candidate table
    (so rejections are explainable, not silent)."""

    def __init__(self, program, cluster, candidates, plan,
                 worker_programs, worker_startups, proof_diagnostics,
                 fallback=False):
        self.program = program
        self.cluster = cluster
        self.candidates = candidates        # [PricedCandidate], ranked
        self.plan = plan                    # the chosen PricedCandidate
        self.worker_programs = worker_programs
        self.worker_startups = worker_startups
        self.proof_diagnostics = list(proof_diagnostics)
        self.fallback = bool(fallback)

    @property
    def deadlock_free(self):
        return self.plan is not None and self.plan.deadlock == "ok"

    def to_dict(self, canonical=False):
        return {
            "cluster": self.cluster.to_dict(),
            "plan": self.plan.to_dict(canonical=canonical)
            if self.plan else None,
            "fallback": self.fallback,
            "candidates": [c.to_dict(canonical=canonical)
                           for c in self.candidates],
        }

    def to_json(self):
        """Canonical byte-stable serialization — the determinism
        contract: same (program, ClusterSpec) → identical bytes in any
        process, autotune on or off.  Prices serialize in CANONICAL
        form (calibration divided back out): a cached calibration
        factor scales every candidate alike — it cannot flip the
        ranking — so the canonical bytes stay invariant to the cache
        state while ``to_dict()`` keeps the calibrated numbers for the
        CLI."""
        return json.dumps(self.to_dict(canonical=True), sort_keys=True,
                          separators=(",", ":"))

    def format_table(self):
        """Human candidate table: predicted step cost, ICI bytes, peak
        HBM, deadlock verdict, chosen/rejected reason."""
        lines = [
            "auto-parallelism plan for %r:" % (self.cluster,),
            "  %-44s %10s %12s %5s %12s %8s  %s" % (
                "candidate", "step ms", "ICI bytes", "quant",
                "peak HBM", "deadlock", "verdict"),
        ]
        for pc in self.candidates:
            lines.append("  %-44s %10.3f %12d %5s %12d %8s  %s" % (
                pc.candidate.describe()[:44], pc.price.step_ms,
                pc.price.ici_bytes,
                "int8" if getattr(pc.candidate, "quant", False) else "-",
                pc.price.peak_memory_bytes,
                pc.deadlock or "-",
                ("CHOSEN: " if pc.chosen else "") + pc.status))
        if self.fallback:
            lines.append(
                "  (no candidate fits the %s-byte HBM budget — degraded "
                "to the least-memory plan)" % (self.plan.budget,))
        return "\n".join(lines)

    def tier_wire_table(self):
        """Per-ring wire rows (ring -> tier, bytes, ms, quant) of the
        winner's REALIZED schedule — the hierarchy rewrite applied when
        the winner carries ``hier`` — priced on the cluster's topology
        tiers.  None when the spec is flat (no tiers to split across)
        or no plan was chosen; ``analyze_program --plan`` prints these
        rows in text and under ``plan.tier_wire_table`` in ``--json``."""
        if not getattr(self.cluster, "has_topology", False):
            return None
        if self.plan is None or not self.worker_programs:
            return None
        from ..static_analysis.cost import (estimate_cost,
                                            tier_wire_table)

        cand = self.plan.candidate
        w0 = self.worker_programs[0]
        if getattr(cand, "hier", False):
            w0 = _hier_proof_twin(w0, cand, self.cluster) or w0
        try:
            report = estimate_cost(w0, nranks=max(cand.degree, 2))
        except Exception:  # a table, not a gate — degrade to nothing
            return None
        return tier_wire_table(report, self.cluster)

    def runtime_config(self):
        """``(BuildStrategy, env)`` realizing the chosen plan's runtime
        knobs: ZeRO-1 optimizer-state sharding and the allreduce bucket
        cap as the ``PADDLE_TPU_ALLREDUCE_BUCKET_MB`` env the fusion
        pass falls back to — the manual/multi-process deployment form
        (:func:`apply_plan` scopes the same bucket to one program via
        the ``_allreduce_bucket_mb`` mark instead)."""
        from ..compiler import BuildStrategy

        bs = BuildStrategy()
        c = self.plan.candidate
        bs.shard_optimizer_state = c.zero1
        env = {}
        if c.bucket_mb:
            bs.fuse_all_reduce_ops = True
            env["PADDLE_TPU_ALLREDUCE_BUCKET_MB"] = str(c.bucket_mb)
        if getattr(c, "quant", False):
            mark = quant_bucket_mark(self.cluster, c.degree)
            env["PADDLE_TPU_QUANT_MIN_BYTES"] = str(mark["min_bytes"])
            env["PADDLE_TPU_QUANT_BLOCK"] = str(mark["block"])
        from ..static_analysis.overlap import overlap_enabled
        if overlap_enabled():
            # the overlap axis was searched: the env realizes the
            # verdict either way (a plan priced synchronous must not
            # silently run overlapped); kill switch off → key absent
            env["PADDLE_TPU_OVERLAP"] = \
                "1" if getattr(c, "overlap", False) else "0"
        from ..static_analysis.hierarchy import hierarchy_enabled
        if getattr(self.cluster, "has_topology", False) \
                and hierarchy_enabled():
            # same realize-the-verdict discipline for the hierarchy
            # axis; the spec env carries the topology the deployment's
            # resolve needs to compute the slice groups
            env["PADDLE_TPU_HIERARCHY"] = \
                "1" if getattr(c, "hier", False) else "0"
            env["PADDLE_TPU_CLUSTER_SPEC"] = json.dumps(
                self.cluster.to_dict(), sort_keys=True)
        return bs, env

    def __repr__(self):
        return "PlanResult(%s, %d candidate(s), deadlock_free=%s)" % (
            self.plan.candidate.describe() if self.plan else None,
            len(self.candidates), self.deadlock_free)


# ---------------------------------------------------------------------------
# enumeration
# ---------------------------------------------------------------------------

def _bucket_candidates_mb():
    """Allreduce bucket sizes to search (MB).  Env
    ``PADDLE_TPU_PLAN_BUCKETS_MB`` ("8,32,128") overrides."""
    raw = os.environ.get("PADDLE_TPU_PLAN_BUCKETS_MB", "").strip()
    if raw:
        vals = sorted({max(1, int(float(v))) for v in raw.split(",")
                       if v.strip()})
        if vals:
            return vals
    return [8, 32, 128]


def _stage_counts(chips):
    """Pipeline depths to search: divisors of the chip count in
    [2, min(chips, 8)] — deeper pipelines exceed the bubble regime the
    GPipe schedule model is honest about."""
    return [s for s in range(2, min(chips, 8) + 1) if chips % s == 0]


def _optimizer_state_overrides(program, parts):
    """ZeRO-1 candidate seeding: every optimizer-state persistable
    (moment/velocity accumulators, marked ``_is_optimizer_state`` by
    the optimizer) pinned SHARDED over the data axis — the interp then
    prices the per-worker shard, which is exactly what
    ``BuildStrategy.shard_optimizer_state`` realizes at run time."""
    overrides = {}
    for block in program.blocks:
        for name, var in block.vars.items():
            if getattr(var, "_is_optimizer_state", False) \
                    and var.persistable:
                overrides[name] = Sharding.sharded(DATA_AXIS, 0, parts)
    return overrides


def _has_backward(program):
    return any(
        op.attrs.get("op_role") == "backward" or op.type.endswith("_grad")
        for op in program.global_block().ops)


def _microbatch_count(stages):
    raw = os.environ.get("PADDLE_TPU_PLAN_MICROBATCHES", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            pass
    return 4 * stages


# ---- pipeline cut-point search ----

def _forward_loads(program, base_interp, base_report):
    """Per-forward-op total load (own FLOPs + the grad twins', located
    via ``__fwd_op_id__`` like ``transpile_pipeline``'s stage
    assignment) and the candidate cut boundaries.

    Returns ``(loads, boundaries)``: ``loads[i]`` is the load of the
    i-th forward op of the global block; ``boundaries`` is a list of
    ``(fwd_pos, cut_var_name, cut_bytes)`` — cutting after ``fwd_pos``
    by naming ``cut_var_name`` reproduces exactly the stage assignment
    ``transpile_pipeline`` derives from that cut var.
    """
    flops_by_coord = {}
    for c in base_report.op_costs:
        flops_by_coord[(c.record.block_idx, c.record.op_idx)] = c.flops

    block = program.global_block()
    fwd_pos_by_op_id = {}
    loads = []
    fwd_ops = []
    for op_idx, op in enumerate(block.ops):
        if op.attrs.get("op_role") in ("backward", "optimize",
                                       "lr_sched") \
                or op.type.endswith("_grad"):
            continue
        fwd_pos_by_op_id[op.attrs.get("__op_id__")] = len(fwd_ops)
        fwd_ops.append((op_idx, op))
        loads.append(flops_by_coord.get((0, op_idx), 0))
    # fold each grad op's FLOPs onto its forward twin's position
    for op_idx, op in enumerate(block.ops):
        fwd_id = op.attrs.get("__fwd_op_id__")
        if fwd_id is None or fwd_id not in fwd_pos_by_op_id:
            continue
        loads[fwd_pos_by_op_id[fwd_id]] += flops_by_coord.get(
            (0, op_idx), 0)

    # candidate boundaries: ARTICULATION POINTS of the forward dataflow
    # — positions where exactly ONE non-persistable, non-data value is
    # live across the cut (produced before, read after).  Cutting
    # anywhere else makes several activations cross the stage edge;
    # ``transpile_pipeline`` then emits multiple p2p edges per channel
    # whose send/recv orders can interleave into exactly the rendezvous
    # deadlocks the prover rejects (it DID reject them — this
    # restriction keeps the search inside the provable region, the
    # residual-stream layer boundaries of a transformer).
    def _crosses(name):
        var = block._find_var_recursive(name)
        if var is None or var.persistable or var.is_data:
            return False
        return True

    prod_pos = {}
    last_read_pos = {}
    for pos, (op_idx, op) in enumerate(fwd_ops):
        for n in op.input_arg_names:
            if n in prod_pos:
                last_read_pos[n] = pos
        for n in op.output_arg_names:
            prod_pos.setdefault(n, pos)
    boundaries = []
    for pos in range(len(fwd_ops) - 1):
        live = [n for n in prod_pos
                if _crosses(n) and prod_pos[n] <= pos
                and last_read_pos.get(n, -1) > pos]
        if len(live) != 1:
            continue
        n = live[0]
        av = base_interp.val(n)
        if av is None or av.shape is None or av.numel is None:
            continue
        boundaries.append((pos, n, av.numel * dtype_bytes(av.dtype)))
    # transpile_pipeline cuts when the cut var first appears in a
    # forward op's outputs: only the FIRST live position of each var
    # reproduces that stage assignment
    seen = set()
    firsts = []
    for pos, n, nbytes in boundaries:
        if n in seen:
            continue
        seen.add(n)
        firsts.append((pos, n, nbytes))
    return loads, firsts


def _thin_boundaries(loads, boundaries, cap=64):
    """Bound the branch-and-bound: keep at most ``cap`` boundaries,
    the ones closest to evenly spaced cumulative-load quantiles
    (deterministic)."""
    if len(boundaries) <= cap:
        return boundaries
    prefix = [0]
    for v in loads:
        prefix.append(prefix[-1] + v)
    total = prefix[-1] or 1
    kept = []
    kept_idx = set()
    for q in range(1, cap + 1):
        target = total * q / (cap + 1)
        best = min(
            range(len(boundaries)),
            key=lambda i: (abs(prefix[boundaries[i][0] + 1] - target),
                           boundaries[i][0], boundaries[i][1]))
        if best not in kept_idx:
            kept_idx.add(best)
            kept.append(boundaries[best])
    kept.sort()
    return kept


def _best_cuts(loads, boundaries, stages):
    """Pick ``stages-1`` cut boundaries minimizing the max per-stage
    fwd+bwd load — branch-and-bound over the boundary lattice (exact
    dynamic program with dominance pruning), tie-broken by smaller
    total cut bytes then lexicographic cut names, so the same inputs
    always select the same cuts.  Returns the cut-var name tuple, or
    None when there are not enough boundaries."""
    k = stages - 1
    if k <= 0 or len(boundaries) < k:
        return None
    prefix = [0]
    for v in loads:
        prefix.append(prefix[-1] + v)
    n_ops = len(loads)

    def seg(a, b):  # load of fwd ops [a, b)
        return prefix[b] - prefix[a]

    # dp[(j)] after choosing c cuts ending at boundary j:
    # (max_load_so_far, cut_bytes_so_far, cut_names) — minimize
    # lexicographically; positions strictly increase
    best = {}
    for j, (pos, name, nbytes) in enumerate(boundaries):
        best[j] = (seg(0, pos + 1), nbytes, (name,), pos)
    for c in range(1, k):
        nxt = {}
        for j, (pos, name, nbytes) in enumerate(boundaries):
            cand = None
            for i, state in best.items():
                ppos = state[3]
                if ppos >= pos:
                    continue
                key = (max(state[0], seg(ppos + 1, pos + 1)),
                       state[1] + nbytes, state[2] + (name,), pos)
                if cand is None or key[:3] < cand[:3]:
                    cand = key
            if cand is not None:
                nxt[j] = cand
        best = nxt
        if not best:
            return None
    final = None
    for state in best.values():
        key = (max(state[0], seg(state[3] + 1, n_ops)),
               state[1], state[2])
        if final is None or key < final:
            final = key
    return final[2] if final else None


def enumerate_candidates(program, cluster, base_interp=None,
                         base_report=None, batch_size=None):
    """The deterministic candidate list for ``program`` on ``cluster``.
    Pipeline cut points are searched here (bounded branch-and-bound
    over layer-boundary loads); pricing happens in
    :func:`auto_transpile`."""
    chips = cluster.chips
    if chips <= 1:
        return [PlanCandidate("single", 1)]
    if base_interp is None:
        base_interp = interpret_program(program, nranks=1,
                                        batch_size=batch_size)
    if base_report is None:
        base_report = estimate_cost(program, interp=base_interp)

    cands = []
    trainable = _has_backward(program)

    # data parallel (with the bucketed-allreduce launch model); ZeRO-1
    # variant only when there is optimizer state to shard
    buckets = _bucket_candidates_mb()
    has_opt_state = bool(_optimizer_state_overrides(program, chips))
    # int8 block-quantized gradient exchange is one more per-bucket
    # dimension of the same dp family (EQuARX; the ``quant`` subsystem);
    # only trainable programs have gradients to quantize, and the
    # PADDLE_TPU_QUANT=0 kill switch removes the axis entirely so plans
    # (and their byte-stable to_json) are identical to the pre-quant
    # planner
    from ..quant.blockwise import quant_enabled
    from ..static_analysis.overlap import overlap_enabled

    quant_axis = (False, True) if (trainable and quant_enabled()) \
        else (False,)
    # start/wait collective overlap (ISSUE 16) is the third per-bucket
    # dimension; it interacts with both others — a bigger bucket hides
    # more wire under one window but defines later (smaller window),
    # and quantization shrinks the wire a window must hide.  The
    # PADDLE_TPU_OVERLAP=0 kill switch removes the axis entirely so
    # plans stay byte-stable against the pre-overlap planner.
    overlap_axis = (False, True) if (trainable and overlap_enabled()) \
        else (False,)
    # hierarchical decomposition (ISSUE 18) is the fourth per-bucket
    # dimension — only meaningful when the cluster HAS a topology and
    # the dp ring would span slices (DP across the slow tier; the
    # model/pipeline/bucket/quant/overlap axes stay inside the fast
    # tier).  PADDLE_TPU_HIERARCHY=0 removes the axis entirely, and a
    # flat (no-topology) ClusterSpec never grows it — plans stay
    # byte-stable against the pre-hierarchy planner.
    from ..static_analysis.hierarchy import hierarchy_enabled

    hier_axis = (False, True) if (
        trainable and hierarchy_enabled()
        and getattr(cluster, "has_topology", False)
        and chips > cluster.chips_per_slice) else (False,)
    for bucket in buckets:
        for q in quant_axis:
            for ov in overlap_axis:
                for h in hier_axis:
                    cands.append(PlanCandidate(
                        "dp", chips, bucket_mb=bucket,
                        quant=q, overlap=ov, hier=h))
                    if trainable and has_opt_state:
                        cands.append(PlanCandidate(
                            "dp", chips, bucket_mb=bucket,
                            zero1=True, quant=q, overlap=ov, hier=h))

    # pipeline splits over searched layer boundaries
    loads, boundaries = _forward_loads(program, base_interp, base_report)
    boundaries = _thin_boundaries(loads, boundaries)
    for stages in _stage_counts(chips):
        cuts = _best_cuts(loads, boundaries, stages)
        if cuts is None:
            continue
        cands.append(PlanCandidate(
            "pipeline", chips, stages=stages, dp_degree=chips // stages,
            cuts=cuts, microbatches=_microbatch_count(stages)))

    # moe / ulysses replication — applicable when the program already
    # expresses that parallelism (the emitters stamped their all_to_all
    # ops with the family's comm_tag) AND the program is not a trainer:
    # plain replication of a TRAINABLE program has no gradient
    # exchange, so it would always price below dp (same compute, no
    # allreduce) while silently training N divergent replicas — a
    # trainable expert/sequence-parallel placement needs its gradient
    # topology expressed in the program (the dp candidates above
    # GradAllReduce the same moe/ulysses program and stay sound)
    if not trainable:
        comm_tags = {
            str(op.attrs.get("comm_tag", ""))
            for b in program.blocks for op in b.ops
            if op.type == "all_to_all"}
        if any(t.startswith(_MOE_COMM_TAGS) for t in comm_tags):
            cands.append(PlanCandidate("moe", chips))
        if any(t.startswith(_ULYSSES_COMM_TAGS) for t in comm_tags):
            cands.append(PlanCandidate("ulysses", chips))

    cands.sort(key=lambda c: c.plan_key())
    return cands


# ---------------------------------------------------------------------------
# emission (through the existing per-strategy emitters)
# ---------------------------------------------------------------------------

def _prune_foreign_persistables(worker, startup=None):
    """Drop persistable vars no op of this worker references (other
    stages' parameters survive ``transpile_pipeline``'s clone) so the
    per-stage peak-memory estimate reflects what the stage actually
    holds — and prune the matching ``startup`` the same way: a startup
    that still initializes EVERY parameter would materialize the whole
    model on each stage, making the pruned feasibility estimate a lie
    at deploy time."""
    referenced = set()
    for block in worker.blocks:
        for op in block.ops:
            referenced.update(op.input_arg_names)
            referenced.update(op.output_arg_names)

    def keep(v, n):
        return n in referenced or not v.persistable or v.is_data

    for block in worker.blocks:
        block.vars = {n: v for n, v in block.vars.items()
                      if keep(v, n)}
    worker._bump_version()
    if startup is not None:
        sb = startup.global_block()
        dropped = {
            n for n, v in sb.vars.items()
            if v.persistable and not keep(v, n)
            # comm-ring bootstrap vars belong to the startup itself
            and not n.startswith("tpu_comm_id_")}
        sb.ops = [op for op in sb.ops
                  if not (set(op.output_arg_names) & dropped)]
        sb.vars = {n: v for n, v in sb.vars.items()
                   if n not in dropped}
        startup._bump_version()
    return worker


def _emit(program, startup_program, cand, cluster, limit=None):
    """Realize one candidate as per-worker (main, startup) program
    pairs via the existing emitters.  Emitted mains carry
    ``_auto_plan_key`` so downstream tooling (and the
    ``manual-plan-suboptimal`` advisory) can tell planner output from
    hand transpiles.  ``limit`` caps the emitted rank count for the
    SYMMETRIC kinds (every rank runs the identical program, so pricing
    needs just one clone); pipeline stages differ and always emit in
    full."""
    from ..framework import Program
    from ..transpiler.collective import GradAllReduce, ensure_comm_ring
    from .pipeline import transpile_pipeline

    def _startup_clone():
        return (startup_program.clone()
                if startup_program is not None else Program())

    if cand.kind == "single":
        workers, startups = [program.clone()], [_startup_clone()]
    elif cand.kind == "dp":
        workers, startups = [], []
        for rank in range(min(cand.degree, limit or cand.degree)):
            m = program.clone()
            s = _startup_clone()
            GradAllReduce().transpile(program=m, startup_program=s,
                                      rank=rank, nranks=cand.degree)
            m._num_trainers = cand.degree
            m._trainer_id = rank
            if cand.zero1:
                m._shard_optimizer_state = True
            workers.append(m)
            startups.append(s)
    elif cand.kind == "pipeline":
        workers, startups = transpile_pipeline(
            program, list(cand.cuts), startup_program=startup_program)
        workers = [_prune_foreign_persistables(w, startup=s)
                   for w, s in zip(workers, startups)]
        if cand.dp_degree > 1:
            # hierarchical: each stage is itself data-parallel over
            # chips/stages ranks — grad allreduce on ring 0 within the
            # stage's DP subgroup (every subgroup member runs the
            # identical stage program).  _num_trainers carries the DP
            # degree so pricing interprets the stage at its LOCAL batch
            # shard with ring-0 ICI at the subgroup size, not the
            # full-batch/stage-count mispricing
            for w, s in zip(workers, startups):
                GradAllReduce().transpile(program=w, startup_program=s,
                                          rank=0,
                                          nranks=cand.dp_degree)
                w._num_trainers = cand.dp_degree
    else:  # moe / ulysses replication
        workers, startups = [], []
        rings = sorted({
            op.attrs.get("ring_id")
            for b in program.blocks for op in b.ops
            if op.attrs.get("ring_id") is not None})
        for rank in range(min(cand.degree, limit or cand.degree)):
            m = program.clone()
            m._num_trainers = cand.degree
            m._trainer_id = rank
            s = _startup_clone()
            for ring in rings:
                ensure_comm_ring(s, ring, rank=rank, nranks=cand.degree)
            workers.append(m)
            startups.append(s)
    for w in workers:
        w._auto_plan_key = repr(cand.plan_key())
    return workers, startups


# ---------------------------------------------------------------------------
# pricing
# ---------------------------------------------------------------------------

def _combine_prices(prices):
    """Plan price of a multi-worker (pipeline) schedule: every stage
    runs concurrently, so each roofline component is the max over
    workers; the step total re-derives from the maxima."""
    from ..static_analysis.cost import PlanPrice, plan_calibration_factor

    calibration = plan_calibration_factor()
    flops_ms = max(p.flops_ms for p in prices)
    hbm_ms = max(p.hbm_ms for p in prices)
    compute_ms = max(p.compute_ms for p in prices)
    ici_ms = max(p.ici_ms for p in prices)
    launch_ms = max(p.launch_ms for p in prices)
    step_ms = (compute_ms + ici_ms + launch_ms) * calibration
    return PlanPrice(
        flops_ms, hbm_ms, compute_ms, ici_ms, launch_ms, step_ms,
        max(p.ici_bytes for p in prices),
        max(p.peak_memory_bytes for p in prices),
        max(p.collective_launches for p in prices),
        max(p.schedule_factor for p in prices), calibration)


def _param_allgather_bytes(program, nranks):
    """Per-worker ICI volume of the ZeRO-1 param allgather: every
    parameter's update is computed on its owning shard and gathered to
    all, a ``B·(n-1)/n`` ring transfer of the full parameter bytes."""
    from .. import framework

    total = 0
    for block in program.blocks:
        for var in block.vars.values():
            if isinstance(var, framework.Parameter) and var.shape:
                n = 1
                for d in var.shape:
                    n *= max(int(d), 1)
                total += n * dtype_bytes(var.dtype)
    n = max(int(nranks), 1)
    return int(total * (n - 1) / n)


def _bucketed_launches(report, bucket_mb):
    """Launch count under size-capped allreduce coalescing: ring-0
    allreduce payloads pack into ``bucket_mb`` buckets (the PR-5
    ``c_fused_allreduce_sum`` rewrite); other collectives launch as
    is."""
    if not bucket_mb:
        return None
    cap = bucket_mb * _MB
    grad_bytes = 0
    grad_launches = 0
    other = 0
    for c in report.op_costs:
        if c.ici_bytes <= 0:
            continue
        if c.record.op.type in ("c_allreduce_sum",
                                "c_fused_allreduce_sum") \
                and (c.ring_id in (0, None)):
            payload = sum(
                (v.local_numel or 0) * dtype_bytes(v.dtype)
                for v in c.record.ins)
            grad_bytes += payload
            grad_launches += 1
        else:
            other += 1
    if not grad_launches:
        return None
    return other + max(1, int(math.ceil(grad_bytes / float(cap))))


def _quant_price_delta(report, nranks, bucket_mb):
    """(ici_delta_bytes, extra_launches) of int8-quantizing the ring-0
    gradient exchange: delta is NEGATIVE (bytes saved) and the launch
    tax covers the extra collective phase plus the quant/dequant
    kernels per bucket — what makes a compute-bound (small-payload)
    program price quant as losing."""
    from ..quant.blockwise import quant_block
    from ..quant.collective import quantized_wire_bytes
    from ..static_analysis.cost import collective_ici_bytes

    grad_numel = 0
    dense_bytes = 0
    launches = 0
    for c in report.op_costs:
        if c.ici_bytes <= 0:
            continue
        if c.record.op.type in ("c_allreduce_sum",
                                "c_fused_allreduce_sum") \
                and (c.ring_id in (0, None)):
            members = [v for v in c.record.ins
                       if str(v.dtype) in ("float32", "bfloat16")]
            if not members:
                continue
            grad_numel += sum(v.local_numel or 0 for v in members)
            dense_bytes += sum(
                (v.local_numel or 0) * dtype_bytes(v.dtype)
                for v in members)
            launches += 1
    if not grad_numel:
        return 0, 0
    wire, _ = quantized_wire_bytes(grad_numel, nranks,
                                   block=quant_block())
    delta = (collective_ici_bytes("c_allreduce_quant", wire, nranks)
             - collective_ici_bytes("c_allreduce_sum", dense_bytes,
                                    nranks))
    if bucket_mb:
        buckets = max(1, int(math.ceil(dense_bytes
                                       / float(bucket_mb * _MB))))
    else:
        buckets = launches
    # per bucket: 1 extra collective phase (scatter+gather vs one psum)
    # + quantize + dequantize kernel launches
    return delta, 3 * buckets


def _hier_price_delta(report, cluster, nranks, bucket_mb, quant):
    """Per-tier pricing delta of hierarchically decomposing the ring-0
    gradient exchange on ``cluster``: returns ``(extra_tier_bytes,
    tier_launches, extra_launches)`` or ``(None, None, 0)`` when
    nothing decomposes.

    The flat report's ring-0 ops price their FULL volume at the slow
    tier (``_op_tier`` maps a ring of ``nranks > chips_per_slice``
    participants to DCN); the decomposition replaces that with
    intra-slice RS + AG (``2·B·(c-1)/c`` at ICI) plus a cross-slice
    allreduce of the 1/c chunk (``2·(B/c)·(s-1)/s`` at DCN — int8 wire
    when the candidate quantizes, the hop where EQuARX pays most).  So
    the delta ADDS the ICI volume and SUBTRACTS the flat DCN volume in
    favor of the chunk exchange."""
    from ..quant.blockwise import quant_block
    from ..quant.collective import quantized_wire_bytes
    from ..static_analysis.cost import collective_ici_bytes

    c = max(int(cluster.chips_per_slice), 1)
    s = max(nranks // c, 1)
    if s <= 1:
        return None, None, 0
    grad_numel = 0
    dense_bytes = 0
    flat_ici = 0
    launches = 0
    for oc in report.op_costs:
        if oc.ici_bytes <= 0:
            continue
        if oc.record.op.type in ("c_allreduce_sum",
                                 "c_fused_allreduce_sum",
                                 "c_allreduce_quant") \
                and (oc.ring_id in (0, None)):
            members = oc.record.ins
            grad_numel += sum(v.local_numel or 0 for v in members)
            dense_bytes += sum(
                (v.local_numel or 0) * dtype_bytes(v.dtype)
                for v in members)
            flat_ici += oc.ici_bytes
            launches += 1
    if not grad_numel:
        return None, None, 0
    if bucket_mb:
        buckets = max(1, int(math.ceil(dense_bytes
                                       / float(bucket_mb * _MB))))
    else:
        buckets = launches
    chunk_numel = -(-grad_numel // c)
    chunk_bytes = -(-dense_bytes // c)
    # RS and AG each move the full bucket around the slice ring
    ici_add = 2 * collective_ici_bytes("c_allgather", dense_bytes, c)
    if quant:
        wire, _ = quantized_wire_bytes(chunk_numel, s,
                                       block=quant_block())
        cross = collective_ici_bytes("c_allreduce_quant", wire, s)
    else:
        cross = collective_ici_bytes("c_allreduce_sum", chunk_bytes, s)
    extra_tier = {"ici": ici_add, "dcn": cross - flat_ici}
    tier_launches = {"dcn": buckets}
    extra = 2 * buckets           # 3 collective phases where 1 fired
    if quant:
        extra += 3 * buckets      # quant/dequant kernels on the hop
    return extra_tier, tier_launches, extra


def _overlap_windows(worker, cand, cluster, nranks, targets,
                     batch_size=None):
    """Overlap windows of the bucketed-fusion + start/wait rewrite this
    candidate would actually run with, extracted from a throwaway
    pricing clone carrying the candidate's bucket/quant/overlap marks
    (NOT the worker's env) — exact windows, not a byte-delta model,
    because the window's hideable wire depends on where liveness lets
    the start hoist, which only the real rewrite knows.  Returns ()
    when the rewrite yields no window (tiny program, proof revert, no
    multi-member bucket): the candidate then prices identically to its
    synchronous twin and loses the ``plan_key`` tie-break.

    Only the allreduce bucketing family runs on the pricing clone: the
    compute-side fusions (attention, elewise, …) preserve the window's
    FLOPs and don't move collectives, so skipping their pattern
    matching changes nothing the window model reads while cutting the
    per-candidate rewrite cost ~2x (bert_base: the search stays inside
    the determinism test's 30 s CPU budget)."""
    from ..static_analysis.fusion import FusionConfig, apply_fusion_passes
    from ..static_analysis.overlap import apply_overlap_pass
    from ..static_analysis.verifier import set_pass_verification

    # the clone is a throwaway meter, never executed or returned: the
    # per-pass verify bracket (PADDLE_TPU_VERIFY_PASSES=1 in the test
    # suite) would re-lint bert_base once per candidate for nothing
    prev = set_pass_verification(False)
    try:
        clone = worker.clone()
        clone._allreduce_bucket_mb = cand.bucket_mb
        clone._overlap = True
        if getattr(cand, "quant", False):
            clone._quant_buckets = quant_bucket_mark(cluster,
                                                     cand.degree)
        tkey = tuple(targets or ())
        cfg = FusionConfig(enabled=True, fuse_attention=False,
                           fuse_elewise=False, fuse_softmax_xent=False,
                           fuse_conv_bn_act=False)
        apply_fusion_passes(clone, cfg, targets=tkey)
        if getattr(cand, "hier", False):
            # a hier+overlap twin's windows come from the DECOMPOSED
            # schedule (the remaining overlappable buckets after the
            # hierarchy rewrite), same as the resolve-time pass order
            from ..static_analysis.hierarchy import apply_hierarchy_pass

            clone._hierarchy = {
                "chips_per_slice": cluster.chips_per_slice}
            apply_hierarchy_pass(clone, targets=tkey, nranks=nranks)
        ov = apply_overlap_pass(clone, targets=tkey, nranks=nranks)
        if not ov.applied:
            return ()
        report = estimate_cost(clone, nranks=nranks, targets=tkey,
                               batch_size=batch_size)
    except Exception:  # pricing must degrade, never crash the search
        return ()
    finally:
        set_pass_verification(prev)
    return tuple(report.overlap_windows)


def quant_bucket_mark(cluster, nranks, dtype_nbytes=4):
    """The ``_quant_buckets`` program mark a quant-winning plan stamps:
    the break-even bucket size (bytes) where the int8 byte cut pays for
    the per-bucket launch tax on THIS cluster, plus the block size the
    plan was priced with.  Buckets below ``min_bytes`` stay bf16 — the
    per-bucket realization of "only ICI-bound buckets win"."""
    from ..quant.blockwise import quant_block

    blk = quant_block()
    n = max(int(nranks), 2)
    wire_per_elem = 1.0 + 4.0 / blk          # int8 + f32-scale sidecar
    saved_per_byte = max(
        (dtype_nbytes - wire_per_elem) / float(dtype_nbytes), 1e-6)
    wire_gbps, launch_us = cluster.ici_gbps, cluster.launch_us
    if getattr(cluster, "has_topology", False) \
            and n > cluster.chips_per_slice:
        # the exchange crosses the slow tier: int8 breaks even where
        # the DCN wire pays for the launch tax (EQuARX prices the hop,
        # not the flat ring) — slower wire → smaller break-even bucket
        wire_gbps, launch_us = cluster.tier_wire().get(
            "dcn", (wire_gbps, launch_us))
    overhead_s = 3 * max(launch_us, cluster.launch_us) * 1e-6
    ring = 2.0 * (n - 1) / n
    min_bytes = overhead_s * wire_gbps * 1e9 / (ring * saved_per_byte)
    return {"min_bytes": max(int(min_bytes), 1), "block": blk}


def price_worker_set(workers, cluster, cand=None, targets=(),
                     batch_size=None, shard_overrides=None,
                     reports=None, _window_cache=None):
    """Price an emitted per-worker program set against ``cluster``;
    returns ``(reports, PlanPrice)``.  Also the entry point the tests
    use to price the HAND-written ``dist_model`` worker builders so
    planner output and manual transpiles meet the same meter.

    A pipeline worker set (stamped ``_pipeline_stage`` by
    ``transpile_pipeline``) gets the GPipe bubble factor
    ``(M+S-1)/M`` whether it came from the planner or a hand
    transpile — both plans pay the same schedule inefficiency."""
    budget = hbm_budget(workers[0]) or cluster.hbm_bytes
    schedule_factor = 1.0
    stages = None
    if cand is not None and cand.kind == "pipeline":
        stages, microbatches = cand.stages, cand.microbatches
    elif getattr(workers[0], "_pipeline_stage", None) is not None:
        stages, microbatches = len(workers), _microbatch_count(
            len(workers))
    if stages is not None:
        m = max(1, microbatches)
        schedule_factor = (m + stages - 1) / float(m)
    precomputed = reports
    reports = []
    prices = []
    for wi, w in enumerate(workers):
        nranks = int(getattr(w, "_num_trainers", 0) or 0) or len(workers)
        if precomputed is not None:
            # the caller already priced this exact worker (an overlap
            # twin reuses its synchronous sibling's emission): the base
            # report is identical by construction, skip the re-estimate
            report = precomputed[wi]
        else:
            interp = interpret_program(w, nranks=nranks,
                                       batch_size=batch_size,
                                       shard_overrides=shard_overrides)
            report = estimate_cost(w, interp=interp, targets=targets,
                                   budget=budget)
        launches = None
        extra_ici = 0
        extra_launches = 0
        extra_tier = None
        tier_launches = None
        if cand is not None:
            launches = _bucketed_launches(report, cand.bucket_mb)
            if cand.zero1:
                # ZeRO-1 is not free speed: sharding the optimizer
                # state means each step allgathers the updated params
                # (no op in the IR carries it — charge it here)
                extra_ici = _param_allgather_bytes(w, cand.degree)
                extra_launches = 1 if extra_ici else 0
            if getattr(cand, "hier", False):
                # hierarchical decomposition reprices the ring-0
                # exchange per tier (the quant axis folds into the
                # cross-slice hop, so _quant_price_delta is skipped)
                extra_tier, tier_launches, hl = _hier_price_delta(
                    report, cluster, nranks, cand.bucket_mb,
                    getattr(cand, "quant", False))
                extra_launches += hl
            elif getattr(cand, "quant", False):
                qd, ql = _quant_price_delta(report, nranks,
                                            cand.bucket_mb)
                if getattr(cluster, "has_topology", False) \
                        and cluster.tier_for(nranks) != "ici":
                    # the flat ring spans the slow tier: the int8 byte
                    # cut applies where those bytes are priced
                    extra_tier = {cluster.tier_for(nranks): qd}
                else:
                    extra_ici += qd
                extra_launches += ql
            if getattr(cand, "overlap", False):
                # exact windows from the rewrite this candidate runs
                # with, attached to the BASE report so the overlap twin
                # differs from its synchronous sibling ONLY by hidden
                # wire (price_plan's max(compute, wire) window model)
                # plus one wait-barrier launch per window.  Cached per
                # (kind, degree, bucket, quant) across the search:
                # zero1 twins share the windows because ZeRO-1 only
                # reshapes the optimizer tail, which sits AFTER every
                # wait sink — the backward region the windows span is
                # byte-identical
                wkey = (cand.kind, cand.degree, cand.dp_degree,
                        cand.bucket_mb,
                        bool(getattr(cand, "quant", False)),
                        bool(getattr(cand, "hier", False)))
                windows = None if _window_cache is None \
                    else _window_cache.get(wkey)
                if windows is None:
                    windows = _overlap_windows(w, cand, cluster, nranks,
                                               targets, batch_size)
                    if _window_cache is not None:
                        _window_cache[wkey] = windows
                if windows:
                    report.overlap_windows = list(windows)
                    extra_launches += len(windows)
        reports.append(report)
        prices.append(price_plan(
            report,
            peak_tflops=cluster.peak_tflops,
            hbm_gbps=cluster.hbm_gbps,
            ici_gbps=cluster.ici_gbps,
            launch_us=cluster.launch_us,
            schedule_factor=schedule_factor,
            collective_launches=launches,
            extra_ici_bytes=extra_ici,
            extra_launches=extra_launches,
            cluster=cluster,
            extra_tier_bytes=extra_tier,
            tier_launches=tier_launches))
    if len(prices) == 1:
        return reports, prices[0]
    return reports, _combine_prices(prices)


def _overlap_twin_key(cand):
    """Candidate identity modulo the overlap axis — pairs each overlap
    twin with the synchronous sibling whose emission/report it can
    reuse."""
    return (cand.kind, cand.degree, cand.stages, cand.dp_degree,
            tuple(cand.cuts or ()), cand.bucket_mb, cand.zero1,
            cand.microbatches, getattr(cand, "quant", False),
            getattr(cand, "hier", False))


def _price_candidate(program, startup_program, cand, cluster, targets,
                     batch_size, reuse=None, window_cache=None):
    """Emit (one rank for the symmetric kinds — every rank runs the
    identical program; all stages for pipeline) and exactly price one
    candidate.  Returns ``(PricedCandidate, workers, startups,
    reports)`` — the emission is reused by the proof loop so no
    candidate is cloned/transpiled twice.

    ``reuse=(workers, startups, reports)`` skips both the emission and
    the base cost estimate: an overlap twin's emitted worker and base
    report are byte-identical to its synchronous sibling's (overlap is
    a resolve-time rewrite, not an emission change), so only the
    pricing deltas differ."""
    if reuse is not None:
        workers, startups, base_reports = reuse
    else:
        workers, startups = _emit(program, startup_program, cand,
                                  cluster, limit=1)
        base_reports = None
    overrides = None
    if cand.zero1:
        overrides = _optimizer_state_overrides(program, cand.degree)
    reports, price = price_worker_set(
        workers, cluster, cand=cand, targets=targets,
        batch_size=batch_size, shard_overrides=overrides,
        reports=base_reports, _window_cache=window_cache)
    budget = hbm_budget(program) or cluster.hbm_bytes
    return (PricedCandidate(cand, price, budget), workers, startups,
            reports)


# ---------------------------------------------------------------------------
# the proof, scoped per ring family
# ---------------------------------------------------------------------------

def _hier_proof_twin(worker, cand, cluster):
    """The decomposed schedule a ``hier`` candidate actually runs: a
    throwaway resolve twin (allreduce bucketing + the hierarchy pass,
    exactly the resolve-time order) whose rings 5/6 the deadlock proof
    extracts.  Returns None when the rewrite yields nothing — the
    proof then covers the flat schedule the candidate degrades to."""
    from ..static_analysis.fusion import FusionConfig, \
        apply_fusion_passes
    from ..static_analysis.hierarchy import apply_hierarchy_pass
    from ..static_analysis.verifier import set_pass_verification

    prev = set_pass_verification(False)
    try:
        clone = worker.clone()
        clone._num_trainers = cand.degree
        clone._allreduce_bucket_mb = cand.bucket_mb
        clone._hierarchy = {"chips_per_slice": cluster.chips_per_slice}
        if getattr(cand, "quant", False):
            clone._quant_buckets = quant_bucket_mark(cluster,
                                                     cand.degree)
        cfg = FusionConfig(enabled=True, fuse_attention=False,
                           fuse_elewise=False, fuse_softmax_xent=False,
                           fuse_conv_bn_act=False)
        apply_fusion_passes(clone, cfg, targets=())
        if not apply_hierarchy_pass(clone, nranks=cand.degree):
            return None
        return clone
    except Exception:  # the proof must degrade to flat, never crash
        return None
    finally:
        set_pass_verification(prev)


def _prove(cand, workers, batch_size=None, cluster=None):
    """Deadlock-freedom proof for one candidate's worker set.

    Symmetric plans (dp / moe / ulysses / single) and pure pipelines go
    straight through :func:`check_schedule_consistency`.  Hierarchical
    pipeline×dp plans scope the proof: ring-0 grad allreduces live in
    per-stage DP subgroups whose members run the IDENTICAL stage
    program (consistent by construction), so they are filtered before
    the cross-stage p2p proof — feeding them in unscoped would
    fabricate a divergence between stages that never share ring 0.

    Symmetric worker sets are byte-identical clones of one transpile,
    so worker 0's schedule is extracted ONCE and replicated to the
    candidate's full degree — the proof stays an N-worker consistency
    check without paying N abstract interpretations (or even N
    emissions) of the same program.
    """
    if cand.kind != "pipeline":
        w0 = workers[0]
        if getattr(cand, "hier", False) and cluster is not None \
                and getattr(cluster, "has_topology", False):
            # prove the DECOMPOSED schedule (rings 5/6), not the flat
            # emission the resolve-time rewrite replaces
            w0 = _hier_proof_twin(w0, cand, cluster) or w0
        s0 = extract_collective_schedule(w0, worker=0,
                                         nranks=cand.degree,
                                         batch_size=batch_size)
        schedules = [s0] * cand.degree
        return schedules, check_schedule_consistency(schedules)
    nranks = len(workers)
    schedules = [
        extract_collective_schedule(p, worker=w, nranks=nranks,
                                    batch_size=batch_size)
        for w, p in enumerate(workers)
    ]
    if cand.kind == "pipeline" and cand.dp_degree > 1:
        schedules = [
            {ring: evs for ring, evs in sched.items() if ring != 0}
            for sched in schedules
        ]
    return schedules, check_schedule_consistency(schedules)


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------

def auto_transpile(program, cluster_spec, startup_program=None,
                   targets=None, batch_size=None):
    """Search the placement/sharding space for ``program`` on
    ``cluster_spec`` and return a :class:`PlanResult`: the cheapest
    feasible candidate that the deadlock prover accepts, its per-worker
    programs emitted through the existing emitters, and the full priced
    candidate table.

    * Candidates over the HBM budget are pruned (kept in the table,
      marked); if nothing fits, the planner DEGRADES to the
      least-memory candidate (``result.fallback``) instead of raising.
    * Deterministic: same (program, ClusterSpec) → the byte-identical
      ``result.to_json()`` in any process, autotune on or off (a
      calibration factor scales every candidate alike, so even a
      calibrated cache cannot flip a ranking).
    """
    cluster = ClusterSpec.coerce(cluster_spec)
    targets = targets or ()
    base_interp = interpret_program(program, nranks=1,
                                    batch_size=batch_size)
    base_report = estimate_cost(program, interp=base_interp,
                                targets=targets)
    cands = enumerate_candidates(program, cluster,
                                 base_interp=base_interp,
                                 base_report=base_report,
                                 batch_size=batch_size)

    priced = []
    realized = {}
    sync_twins = {}   # non-overlap (workers, startups, reports) by key
    window_cache = {}
    for cand in cands:
        reuse = None
        if getattr(cand, "overlap", False):
            reuse = sync_twins.get(_overlap_twin_key(cand))
        pc, workers, startups, reports = _price_candidate(
            program, startup_program, cand, cluster, targets,
            batch_size, reuse=reuse, window_cache=window_cache)
        if not getattr(cand, "overlap", False):
            sync_twins[_overlap_twin_key(cand)] = (workers, startups,
                                                   reports)
        realized[cand.plan_key()] = (workers, startups)
        priced.append(pc)

    priced.sort(key=lambda pc: (pc.price.step_ms,
                                pc.candidate.plan_key()))
    feasible = [pc for pc in priced if pc.feasible]
    fallback = not feasible
    if fallback:
        # nothing fits the budget: degrade to the least-memory plan —
        # the planner must never crash on an over-subscribed cluster
        pool = sorted(priced,
                      key=lambda pc: (pc.price.peak_memory_bytes,
                                      pc.candidate.plan_key()))
    else:
        pool = feasible

    winner = None
    winner_set = None
    proof_diags = []
    for pc in pool:
        # the pricing emission is reused: symmetric kinds prove from
        # their single emitted rank (schedule replicated to the full
        # degree), pipelines were emitted in full for pricing anyway;
        # only the accepted WINNER pays a full symmetric emission
        workers, startups = realized[pc.candidate.plan_key()]
        sch, diags = _prove(pc.candidate, workers,
                            batch_size=batch_size, cluster=cluster)
        if diags:
            pc.deadlock = "divergent"
            pc.status = "rejected: %s" % diags[0].message
            proof_diags.extend(diags)
            continue
        pc.deadlock = "ok"
        pc.chosen = True
        winner = pc
        if pc.candidate.kind != "pipeline" \
                and len(workers) < pc.candidate.degree:
            # only the symmetric kinds were emitted rank-limited for
            # pricing; a pipeline set is already complete (its "degree"
            # counts chips, not stage programs)
            workers, startups = _emit(program, startup_program,
                                      pc.candidate, cluster)
        winner_set = (workers, startups)
        break
    if winner is None:
        raise RuntimeError(
            "auto_transpile: every candidate failed the deadlock "
            "proof — the emitters are inconsistent; diagnostics: %s"
            % [d.message for d in proof_diags[:3]])

    if fallback:
        winner.status = ("hbm-infeasible fallback: least-memory plan "
                         "(peak %d > budget %d)"
                         % (winner.price.peak_memory_bytes,
                            winner.budget))
    else:
        winner.status = "cheapest feasible plan"
    for pc in priced:
        if pc is winner or pc.status:
            continue
        if not pc.feasible:
            pc.status = "over HBM budget (peak %d > %d)" % (
                pc.price.peak_memory_bytes, pc.budget)
        else:
            pc.status = "costlier than winner (+%.1f%%)" % (
                100.0 * (pc.price.step_ms - winner.price.step_ms)
                / max(winner.price.step_ms, 1e-12))

    workers, startups = winner_set
    return PlanResult(program, cluster, priced, winner, workers,
                      startups, proof_diags, fallback=fallback)
