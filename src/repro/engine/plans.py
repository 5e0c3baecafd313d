"""Plan/executor core of the serving engine (DESIGN.md §6).

The paper frames a join-correlation query as *semantics* — top-k, estimator,
scorer, confidence level (§4/§5.3) — evaluated over one sketch index. This
module makes that split structural:

  * `ShapePolicy` is everything **compile-relevant**: array shapes, chunking,
    intersect algorithm, kernel backend, the static top-k width ``k_max`` and
    the prune ladders. Programs are keyed on it (plus batch and index shape)
    and on nothing else.
  * `Request` is everything **per-query**: k, estimator, scorer, prune mode,
    confidence level α, eligibility floor. Its knobs enter the compiled
    program as *traced operands* — a tiny replicated f32 vector
    (`request_operands`) holding one-hot selectors and scalars — so a scorer
    or estimator sweep after warmup costs **zero compiles**: the compile
    cache is O(shapes), not O(semantic configs).

Every program is one composable pipeline

    probe → (filter) → (gather) → score → rank

with four materialisations (the *plans*):

  * ``scan``  — no filter stage: score every candidate (`make_scan_fn`);
  * ``probe`` — stage 1 alone: exact intersection sizes (`make_probe_fn`),
    request-independent by construction;
  * ``prune`` — gather-compact host-selected survivors and score them
    against the resident index (`make_pruned_fn`);
  * ``topm`` — fused probe + on-device per-row top-M filter + gather +
    score in one dispatch (`make_topm_fn`).

All four share the same stage functions below — the probe/intersect
primitives, `score_stats` (the §4.4 scoring tail, routed through
`repro.core.scoring`, its single source) and the `_topk_gathered` rank
stage — so there is exactly one implementation of each stage.

The legacy builders (`repro.engine.query.make_query_fn` and friends) and
both server classes survive as thin deprecated wrappers over these plans.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import scoring as SC
from repro.core.bounds import hoeffding_eligibility_floor
from repro.core.sketch import PAD_KEY
from repro.engine.index import IndexShard
from repro.kernels import ops as K
from repro.kernels.ops import KernelConfig

#: sentinel key hash for padded candidate slots — never matches a real key
#: because real slots are masked separately anyway. Canonically defined in
#: `repro.core.hashing.SENTINEL_HASH`; `_PAD_KEY` survives as the historical
#: local name (re-exported by `repro.engine.query`).
_PAD_KEY = PAD_KEY
#: the same sentinel as a traced-friendly jnp scalar for in-program use
_JPAD = jnp.uint32(PAD_KEY)

#: request-semantics vocabularies: the scorers served by the fused fast path
#: (s3 = bootstrap stays a host-side path, `repro.core.scoring.score`), the
#: §5.3 estimators with an in-program implementation, and the prune plans
FAST_SCORERS = ("s1", "s2", "s4")
ESTIMATORS = ("pearson", "spearman", "rin", "qn")
PRUNE_MODES = ("off", "safe", "topm")

_SCORER_INDEX = {s: i for i, s in enumerate(FAST_SCORERS)}
_ESTIMATOR_INDEX = {e: i for i, e in enumerate(ESTIMATORS)}


# ----------------------------------------------------------------------------
# the config split (DESIGN.md §6): compile-relevant shape policy vs
# per-request query semantics
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapePolicy:
    """Compile-relevant knobs of a serving program (DESIGN.md §6).

    Everything here changes the *shape or structure* of the compiled
    program; nothing here encodes query semantics. Two servers with equal
    `ShapePolicy` (and equal index shape) share every compiled program,
    whatever their default `Request`s are.
    """
    #: static top-k width of the compiled rank stage; any request k ≤ k_max
    #: is served by slicing the program's [.., k_max] output on the host
    k_max: int = 10
    #: candidates scored per inner step; bounds the (chunk × n_q × n) match
    #: tensor on the XLA path (the Pallas kernel tiles the same way in VMEM)
    score_chunk: int = 512
    #: XLA-path intersect: "sortmerge" (O(C·n·log n), no n² tensor — §Perf E2)
    #: or "eqmatrix" (the kernel-shaped reference formulation)
    intersect: str = "sortmerge"
    #: kernel backend; the default follows the mesh's platform
    #: (`resolve_shape`)
    kernels: KernelConfig = KernelConfig()
    #: static survivor width of the fused ``topm`` plan (per device shard)
    prune_m: int = 128
    #: base rung of the compacted-shard capacity ladder ``prune_base · 2^i``
    #: used by the ``prune`` plan — stage-2 dispatch shapes are drawn from
    #: this fixed ladder, so the compile cache stays O(log C) (DESIGN.md §4)
    prune_base: int = 64
    #: stage-1 candidate generation (DESIGN.md §7): "scan" = the containment
    #: scan over every resident column (bit-identical to the pre-source
    #: engine), "inverted" = the QCR-style inverted key index — sub-linear
    #: in corpus size, same exact hit counts (`repro.engine.candidates`) —
    #: "auto" = pick per segment by corpus size (`resolve_candidates`:
    #: inverted at `AUTO_INVERTED_MIN_C`+ columns, scan below). Affects the
    #: stage-1-consuming paths (prune='safe'/'topm', `stage1_hits`,
    #: `search_joinable`); prune='off' is scan by definition
    candidates: str = "scan"
    #: number of mesh devices the plans are built for — a first-class axis
    #: of every compile-cache key, so servers on different-size meshes never
    #: share (or collide on) compiled programs. 0 = unresolved: filled in
    #: from the concrete mesh by `resolve_shape` (the `Server` does this);
    #: a nonzero value is validated against the mesh at plan-build time
    mesh_shards: int = 0
    #: cross-shard rank combine (DESIGN.md §10): "gather" = in-program
    #: all-gather + final top-k (replicated ``[.., k_max]`` outputs — the
    #: historical single-host stage), "host" = each device emits only its
    #: local top-k and the ``[D, k_max]`` merge runs on the host
    #: (`combine_local_topk`); both implement the same total order (score
    #: descending, global id ascending). "auto" resolves to "host" on
    #: multi-device meshes and "gather" on single-device meshes
    combine: str = "auto"


@dataclasses.dataclass(frozen=True)
class Request:
    """Per-request query semantics (paper Defn. 3, §4.3/§4.4, §5.3).

    None of these fields touch the compile cache: k becomes a host-side
    slice of the program's static ``k_max`` rank stage, ``prune`` selects
    which already-compiled plan to dispatch, and the rest ride into the
    program as traced operands (`request_operands`).
    """
    k: int = 10
    estimator: str = "pearson"      # pearson | spearman | rin | qn
    scorer: str = "s4"              # s1 | s2 | s4  (s3 = bootstrap: host path)
    prune: str = "off"              # off | safe | topm
    alpha: float = 0.05
    min_sample: int = 3


_COMBINE_MODES = ("auto", "gather", "host")

#: `ShapePolicy.candidates` vocabulary — "auto" resolves per corpus size
#: (`resolve_candidates`); a concrete source never sees it
CANDIDATE_CHOICES = ("scan", "inverted", "auto")

#: corpus-size crossover of ``candidates="auto"``: BENCH_scaling shows the
#: containment scan winning below ~4k columns, the inverted index above
AUTO_INVERTED_MIN_C = 4096


def resolve_candidates(candidates: str, num_columns: int) -> str:
    """Resolve a `ShapePolicy.candidates` value against a concrete corpus
    size: ``"auto"`` becomes "inverted" at `AUTO_INVERTED_MIN_C` columns or
    more and "scan" below (the BENCH_scaling crossover); explicit values
    pass through. Segment executors resolve on construction — against their
    device-padded column count — so every segment of a mixed-size corpus
    picks its own winner and the resolved value participates in its compile
    keys."""
    if candidates not in CANDIDATE_CHOICES:
        raise ValueError(f"unknown candidate source {candidates!r}: "
                         f"use one of {CANDIDATE_CHOICES}")
    if candidates != "auto":
        return candidates
    return "inverted" if int(num_columns) >= AUTO_INVERTED_MIN_C else "scan"


def resolve_shape(shape: ShapePolicy, mesh,
                  num_columns: Optional[int] = None) -> ShapePolicy:
    """Resolve the context-dependent fields of a `ShapePolicy` against a
    concrete mesh: ``mesh_shards`` is pinned to the device count (validated
    if already set), the kernel backend follows the devices' platform
    (``"auto"`` → the Pallas kernels on a TPU, XLA elsewhere; explicit
    backends stay) and ``combine='auto'`` becomes "host" on multi-device
    meshes, "gather" on single-device ones. When ``num_columns`` is given
    (segment executors pass their device-padded column count),
    ``candidates='auto'`` resolves per `resolve_candidates`; without it the
    value is validated but kept — the `Server` keeps "auto" at the facade
    level and resolves per segment. Executors resolve their policy on
    construction so the resolved values participate in every cache key.
    """
    ndev = int(mesh.devices.size)
    if shape.combine not in _COMBINE_MODES:
        raise ValueError(f"unknown combine mode {shape.combine!r}: "
                         f"use one of {_COMBINE_MODES}")
    if shape.mesh_shards not in (0, ndev):
        raise ValueError(
            f"ShapePolicy.mesh_shards={shape.mesh_shards} does not match "
            f"the {ndev}-device mesh it is being resolved against")
    kernels = resolve_kernels(shape, mesh).kernels
    combine = shape.combine
    if combine == "auto":
        combine = "host" if ndev > 1 else "gather"
    if num_columns is not None:
        candidates = resolve_candidates(shape.candidates, num_columns)
    else:
        if shape.candidates not in CANDIDATE_CHOICES:
            raise ValueError(f"unknown candidate source "
                             f"{shape.candidates!r}: use one of "
                             f"{CANDIDATE_CHOICES}")
        candidates = shape.candidates
    if (shape.mesh_shards, shape.combine, shape.candidates,
            shape.kernels) == (ndev, combine, candidates, kernels):
        return shape
    return dataclasses.replace(shape, mesh_shards=ndev, combine=combine,
                               candidates=candidates, kernels=kernels)


def resolve_kernels(shape, mesh=None):
    """Pin an ``"auto"`` kernel backend to the platform the program runs
    on — the mesh's devices, or the default device when there is no mesh
    (`KernelConfig.resolve`: Pallas on a TPU, XLA elsewhere). ``shape`` is
    anything carrying ``kernels`` (a `ShapePolicy` or a legacy
    QueryConfig); it comes back unchanged when already concrete."""
    dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    kernels = shape.kernels.resolve(dev.platform)
    if kernels == shape.kernels:
        return shape
    return dataclasses.replace(shape, kernels=kernels)


def uses_sortmerge(shape) -> bool:
    """Whether a policy scores through the XLA sortmerge intersect (and so
    consumes the precomputed `PreppedShard`): its resolved kernels are the
    XLA backend and it asks for ``intersect="sortmerge"``. ``shape`` is
    anything carrying ``kernels`` and ``intersect`` (a `ShapePolicy` or a
    legacy QueryConfig)."""
    return not shape.kernels.use_pallas and shape.intersect == "sortmerge"


def _plan_combine(shape: ShapePolicy, ndev: int) -> bool:
    """Validate a plan builder's shape policy against the mesh it is being
    built for and return whether the plan uses the host-side rank combine.
    An unresolved ``combine='auto'`` builds the in-program gather combine —
    the historical behaviour every pre-mesh caller gets."""
    if shape.combine not in _COMBINE_MODES:
        raise ValueError(f"unknown combine mode {shape.combine!r}: "
                         f"use one of {_COMBINE_MODES}")
    if shape.mesh_shards not in (0, ndev):
        raise ValueError(
            f"ShapePolicy.mesh_shards={shape.mesh_shards} does not match "
            f"the {ndev}-device mesh this plan is being built for")
    return shape.combine == "host"


def split_config(qcfg) -> "tuple[ShapePolicy, Request]":
    """Split a legacy `repro.engine.query.QueryConfig` into the (shape,
    request) pair of the plan/executor world. ``k_max`` inherits the legacy
    ``k`` — a program built from the split serves any request with k ≤ that.

    Preserves the historical leniency of the pre-split scoring tail: any
    scorer outside {s1, s2} scored as s4, and any estimator outside the
    four in-program ones (pearson/spearman/rin/qn) falls back to pearson —
    configs that the old servers silently served keep being served (a
    directly-constructed `Request` is still validated strictly by
    `request_operands`). Unknown prune modes raise here, as the old server
    constructors did.
    """
    shape = ShapePolicy(k_max=qcfg.k, score_chunk=qcfg.score_chunk,
                        intersect=qcfg.intersect, kernels=qcfg.kernels,
                        prune_m=qcfg.prune_m, prune_base=qcfg.prune_base)
    if qcfg.prune not in PRUNE_MODES:
        raise ValueError(f"unknown prune mode {qcfg.prune!r}: "
                         f"use one of {PRUNE_MODES}")
    req = Request(k=qcfg.k,
                  estimator=(qcfg.estimator if qcfg.estimator in ESTIMATORS
                             else "pearson"),
                  scorer=(qcfg.scorer if qcfg.scorer in ("s1", "s2")
                          else "s4"),
                  prune=qcfg.prune, alpha=qcfg.alpha,
                  min_sample=qcfg.min_sample)
    return shape, req


def request_operands(req: Request) -> np.ndarray:
    """Encode a `Request`'s in-program knobs as the traced operand vector
    ``f32[4] = [estimator, scorer, alpha, eligibility floor]`` every plan
    program takes as its last argument (replicated; KB-free). Changing any
    of them re-uses the compiled program — that is the whole point."""
    if req.estimator not in _ESTIMATOR_INDEX:
        raise ValueError(f"unknown estimator {req.estimator!r}: "
                         f"use one of {ESTIMATORS}")
    if req.scorer not in _SCORER_INDEX:
        raise ValueError(f"unknown scorer {req.scorer!r}: the fused path "
                         f"serves {FAST_SCORERS} (s3 is the host bootstrap)")
    if req.prune not in PRUNE_MODES:
        raise ValueError(f"unknown prune mode {req.prune!r}: "
                         f"use one of {PRUNE_MODES}")
    return np.asarray([_ESTIMATOR_INDEX[req.estimator],
                       _SCORER_INDEX[req.scorer],
                       float(req.alpha),
                       float(hoeffding_eligibility_floor(req.min_sample))],
                      np.float32)


def coalesce_key(req: Request) -> tuple:
    """Request-compatibility key for admission-queue coalescing
    (`repro.engine.scheduler`): two requests whose keys are equal can ride
    **one** dispatch — they share every traced operand
    (`request_operands`: estimator, scorer, α, eligibility floor) and the
    prune-mode plan selection. ``k`` is deliberately absent: it is a
    host-side slice of the program's static ``k_max``, so a coalesced
    dispatch runs at the group's max k and each member slices its own k
    back out. Validates the request (same errors as `request_operands`),
    so a bad request fails at submit time, not inside a worker."""
    request_operands(req)
    return (req.estimator, req.scorer, req.prune, float(req.alpha),
            int(req.min_sample))


def _unpack_ops(ops):
    """ops f32[4] → (est, scorer, alpha, floor) traced scalars."""
    return ops[0], ops[1], ops[2], ops[3]


# ----------------------------------------------------------------------------
# probe stage: intersect primitives (shared by every plan)
# ----------------------------------------------------------------------------

def _sortmerge_moments(q_kh, q_val, q_mask, kh, vals, mask):
    """Eq-matrix-free intersect (§Perf E2): binary-search each candidate's
    (pre-sorted would be better; here sorted on the fly) keys against the
    query — O(C·n·log n) and, crucially, O(C·n) HBM traffic instead of the
    O(C·n²) equality tensor of the matmul formulation. This is the XLA-path
    default; the Pallas kernel keeps the n² tile in VMEM instead.
    """
    PAD = _JPAD
    # A real key hashing to the PAD sentinel is treated as non-matchable on
    # both the single and batched sortmerge paths (keeps them bit-identical;
    # the sentinel is indistinguishable from padding once sorted).
    q_eff = jnp.where(q_kh != PAD, q_mask, 0.0)
    qk = jnp.where(q_eff > 0, q_kh, PAD)
    order = jnp.argsort(qk)
    qk_s = qk[order]
    qv_s = (q_val * q_eff)[order]
    qm_s = q_eff[order]

    ck = jnp.where(mask > 0, kh, PAD)               # [C, n]
    pos = jnp.searchsorted(qk_s, ck.reshape(-1)).reshape(ck.shape)
    pos = jnp.clip(pos, 0, qk_s.shape[0] - 1)
    hitc = (qk_s[pos] == ck) & (qm_s[pos] > 0) & (mask > 0)   # [C, n]
    w = hitc.astype(jnp.float32)
    a = qv_s[pos] * w                                # query values aligned to candidate slots
    b = vals * w
    mom = jnp.stack([w.sum(-1), a.sum(-1), b.sum(-1), (a * a).sum(-1),
                     (b * b).sum(-1), (a * b).sum(-1)], -1)
    return mom, a, b, w


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PreppedShard:
    """Precomputed candidate-side sort structure for the batched intersect
    (the resident half of the XLA sortmerge path, DESIGN.md §3).

    Both arrays are laid out like the (padded, per-``score_chunk``-block)
    index: for each block of ``chunk`` candidate rows, ``dk`` holds the
    block's sorted distinct-key table (flat length chunk·n, PAD-filled tail)
    and ``sid`` maps every original slot to its segment id in that table
    (``chunk·n`` = the never-written dump column for invalid slots). They
    depend only on (index keys, score_chunk) — compute once per index with
    ``make_prep_fn`` and reuse for every dispatch.
    """
    dk: jnp.ndarray    # u32 [Cp, n]
    sid: jnp.ndarray   # i32 [Cp, n]


def _prep_block(kh, mask):
    """Sort one candidate block's keys into the (dk, sid) lookup structure."""
    Mb = kh.shape[0] * kh.shape[1]
    PAD = _JPAD
    ck = jnp.where(mask > 0, kh, PAD).reshape(-1)            # [Mb]
    sort_idx = jnp.argsort(ck)
    ck_s = ck[sort_idx]
    new_seg = jnp.concatenate([jnp.ones((1,), jnp.int32),
                               (ck_s[1:] != ck_s[:-1]).astype(jnp.int32)])
    seg_sorted = jnp.cumsum(new_seg) - 1                     # [Mb], segment ids
    # dk[s] = key of segment s (every write in a segment carries the same
    # key); unfilled tail stays PAD so dk is ascending end to end
    dk = jnp.full((Mb,), PAD, ck.dtype).at[seg_sorted].set(ck_s)
    # original slot → segment id, via the inverse permutation (scatter, not
    # a second argsort); invalid candidate slots point at the never-written
    # dump column Mb
    rank = jnp.zeros((Mb,), jnp.int32).at[sort_idx].set(
        jnp.arange(Mb, dtype=jnp.int32))
    sid = seg_sorted[rank]
    sid = jnp.where(mask.reshape(-1) > 0, sid, Mb)
    return dk.reshape(kh.shape), sid.reshape(kh.shape).astype(jnp.int32)


def _sortmerge_moments_batched(q_kh, q_val, q_mask, kh, vals, mask, prep=None):
    """Leading-query-axis sortmerge: q_* are [B, n_q], candidates shared.

    This is where batching actually pays: the candidate keys are sorted into
    a distinct-key segment table *shared across the whole batch* (and across
    dispatches, when a precomputed ``prep`` is passed — see ``make_prep_fn``),
    each query's n_q keys binary-search that shared table (1-D searches —
    XLA CPU collapses batch-dim gathers into scalar loops, so a naive
    per-row vmap of `_sortmerge_moments` is slower than the sequential loop
    it replaces), membership lands in a ``[B, D]`` table with one scatter
    per query key, and a shared-index gather fans it back out to
    ``[B, C, n]``.

    Exactness: every float that comes out is either an untouched copy of a
    query/candidate value or a true zero (sketch keys are distinct within a
    row, so each membership cell is written at most once — no accumulation),
    and the final moment sums run over the same slot order as the
    single-query path. Batched results are therefore bit-identical to B
    sequential calls.
    """
    B, nq = q_kh.shape
    C, n = kh.shape
    M = C * n
    # the membership scatter below runs in int32 flat index space
    assert B * (M + 1) < 2**31, (
        f"batch {B} × block {M} overflows int32 scatter indices; "
        f"lower ShapePolicy.score_chunk")
    PAD = _JPAD

    if prep is None:
        dk, sid = _prep_block(kh, mask)
    else:
        dk, sid = prep
    dk = dk.reshape(-1)
    sid = sid.reshape(-1)

    # -- per-query membership: one 1-D search + one scatter per key ---------
    qk = jnp.where(q_mask > 0, q_kh, PAD)                    # [B, nq]
    qv = (q_val * q_mask).reshape(-1)
    pos = jnp.clip(jnp.searchsorted(dk, qk.reshape(-1)), 0, M - 1)
    hit = (dk[pos] == qk.reshape(-1)) & (q_mask.reshape(-1) > 0) \
        & (qk.reshape(-1) != PAD)
    row = jnp.repeat(jnp.arange(B, dtype=jnp.int32), nq) * (M + 1)
    # misses target index B*(M+1): out of bounds → dropped by the scatter
    flat = jnp.where(hit, row + pos.astype(jnp.int32), B * (M + 1))
    q_hit = jnp.zeros((B * (M + 1),), jnp.float32).at[flat].set(1.0)
    q_val_tab = jnp.zeros((B * (M + 1),), jnp.float32).at[flat].set(qv)

    # -- fan back out with the shared per-slot segment ids ------------------
    w = jnp.take(q_hit.reshape(B, M + 1), sid, axis=-1).reshape(B, C, n)
    a = jnp.take(q_val_tab.reshape(B, M + 1), sid, axis=-1).reshape(B, C, n)
    b = vals[None] * w
    mom = jnp.stack([w.sum(-1), a.sum(-1), b.sum(-1), (a * a).sum(-1),
                     (b * b).sum(-1), (a * b).sum(-1)], -1)
    return mom, a, b, w


def _est_select(est, pearson_fn, spearman_fn, rin_fn, qn_fn):
    """Estimator stage selector over the four in-program estimators
    (`ESTIMATORS` order). ``est`` is either a static string (legacy
    specialised programs, e.g. `repro.engine.query.score_shard` — unknown
    strings keep the historical pearson fallback) or a traced scalar from
    the request operand vector — then the branch is a `lax.switch`, so a
    per-request estimator flip re-uses the compiled program and only ever
    executes the branch it asks for."""
    fns = (pearson_fn, spearman_fn, rin_fn, qn_fn)
    if isinstance(est, str):
        table = dict(zip(ESTIMATORS, fns))
        return table.get(est, pearson_fn)()
    idx = jnp.clip(jnp.round(est), 0, len(fns) - 1).astype(jnp.int32)
    return jax.lax.switch(idx, fns)


def _no_tiles():
    return jnp.zeros((2,), jnp.int32)


def _ranked(a, b, w, kind: str, shape: ShapePolicy):
    """A rank-family branch: (r from the fused rank moments, the kernel's
    [live, total] pair tiles for the same mask)."""
    return (K.pearson_from_moments(K.rank_moments(a, b, w, kind,
                                                  shape.kernels)),
            K.rank_tiles(w, shape.kernels))


def _score_block(q_kh, q_val, q_mask, kh, vals, mask, shape: ShapePolicy,
                 est, prep=None):
    """probe stage for one candidate block: moments → (r, m) under the
    requested estimator.

    Query arrays are ``[n_q]`` (single) or ``[B, n_q]`` (batched); candidate
    arrays are always ``[C, n]``. Returns moments ``[..., C, 6]``, r
    ``[..., C]`` and the rank kernel's pair tiles ``i32[2]`` (`_no_tiles`
    unless a rank estimator ran the Pallas kernel).
    """
    batched = q_kh.ndim == 2
    if uses_sortmerge(shape):
        if batched:
            intersect = lambda: _sortmerge_moments_batched(
                q_kh, q_val, q_mask, kh, vals, mask, prep=prep)
        else:
            intersect = lambda: _sortmerge_moments(q_kh, q_val, q_mask, kh,
                                                   vals, mask)
        # The raw moments are needed for m and the §4.3 CI under *every*
        # estimator, so the intersect runs in the main computation (fully
        # fused and parallel; the aligned tensors a/b/w are dead code here
        # and fold away). The traced-switch branches are then deliberately
        # tiny for pearson — XLA:CPU executes a conditional's called
        # computations without the main program's fusion/parallelism, so a
        # heavy branch would cost ~2.5× on the hot scan (measured). The
        # rank/qn branches *recompute* their aligned tensors from the same
        # inputs inside the branch: capturing a/b/w instead would force the
        # main program to materialise them for pearson requests too, and
        # the recompute is noise next to the O(C·n²) fused rank-moments /
        # Qn work. Statically-specialised callers pay nothing either way:
        # XLA CSEs the identical intersects of an inline rank estimator.
        mom = intersect()[0]

        def _ranked_r(kind):
            def _r():
                _, a, b, w = intersect()
                return _ranked(a, b, w, kind, shape)
            return _r

        def _qn_r():
            _, a, b, w = intersect()
            return K.qn_correlation(a, b, w, shape.kernels), _no_tiles()

        r, tiles = _est_select(
            est, lambda: (K.pearson_from_moments(mom), _no_tiles()),
            _ranked_r("spearman"), _ranked_r("rin"), _qn_r)
        return mom, r, tiles
    join = (K.sketch_join_moments_batched if batched else K.sketch_join_moments)
    mom, aligned, hit = join(q_kh, q_val, q_mask, kh, vals, mask,
                             shape.kernels)

    def _ranked_kernel(kind):
        def _r():
            qv = jnp.broadcast_to(q_val[..., None, :] * hit, aligned.shape)
            return _ranked(qv, aligned, hit, kind, shape)
        return _r

    def _qn_kernel():
        qv = jnp.broadcast_to(q_val[..., None, :] * hit, aligned.shape)
        return K.qn_correlation(qv, aligned, hit, shape.kernels), _no_tiles()

    r, tiles = _est_select(
        est, lambda: (K.pearson_from_moments(mom), _no_tiles()),
        _ranked_kernel("spearman"), _ranked_kernel("rin"), _qn_kernel)
    return mom, r, tiles


def _chunk_layout(C: int, score_chunk: int):
    """(chunk, pad, nb) of the candidate streaming loop for a C-row shard."""
    chunk = min(score_chunk, C)
    pad = (-C) % chunk
    return chunk, pad, (C + pad) // chunk


def _shard_stats(q_kh, q_val, q_mask, q_cmin, q_cmax, shard: IndexShard,
                 shape: ShapePolicy, est, alpha,
                 prep: Optional[PreppedShard] = None):
    """Chunked scan over a shard's candidates → (r, m, ci_len), each
    [..., C], and the rank kernel's pair tiles ``i32[2]`` summed over the
    chunks.

    Candidates stream through in ``score_chunk`` blocks under ``lax.map`` so
    the (chunk, n_q, n) match tensor stays O(chunk·n²) regardless of shard
    size (§Perf E1 — a 2 M-column index would otherwise need a TB-scale
    equality tensor per device). Shards whose size is not a chunk multiple
    are padded up with masked candidates (dropped again before returning) —
    memory stays bounded for any C. ``est``/``alpha`` may be traced request
    operands (see `request_operands`) or static values.
    """
    batched = q_kh.ndim == 2
    C = shard.key_hash.shape[0]
    chunk, pad, nb = _chunk_layout(C, shape.score_chunk)
    kh, vals, mask = shard.key_hash, shard.values, shard.mask
    if pad:
        kh = jnp.pad(kh, ((0, pad), (0, 0)), constant_values=_PAD_KEY)
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    Cp = C + pad
    if prep is not None:
        assert prep.dk.shape[0] == Cp, (prep.dk.shape, Cp)
    if nb > 1:
        resh = lambda a: a.reshape((nb, chunk) + a.shape[1:])
        have_prep = prep is not None
        blocks_prep = ((resh(prep.dk), resh(prep.sid)) if have_prep
                       else (jnp.zeros((nb, 0)), jnp.zeros((nb, 0))))

        def one(args):
            ckh, cvals, cmask, cdk, csid = args
            return _score_block(q_kh, q_val, q_mask, ckh, cvals, cmask,
                                shape, est,
                                prep=(cdk, csid) if have_prep else None)

        mom, r, tiles = jax.lax.map(one, (resh(kh), resh(vals), resh(mask),
                                          *blocks_prep))
        tiles = tiles.sum(0)
        # lax.map stacks the chunk axis in front: [nb, ..., chunk, ·] → [..., Cp, ·]
        mom = jnp.moveaxis(mom, 0, -3).reshape(q_kh.shape[:-1] + (Cp, mom.shape[-1]))
        r = jnp.moveaxis(r, 0, -2).reshape(q_kh.shape[:-1] + (Cp,))
        mom = mom[..., :C, :]
        r = r[..., :C]
    else:
        mom, r, tiles = _score_block(q_kh, q_val, q_mask, kh, vals, mask,
                                     shape, est,
                                     prep=(prep.dk, prep.sid)
                                     if prep is not None else None)
    m = mom[..., 0]
    if batched:
        c_lo = jnp.minimum(q_cmin[:, None], shard.col_min[None, :])
        c_hi = jnp.maximum(q_cmax[:, None], shard.col_max[None, :])
    else:
        c_lo = jnp.minimum(q_cmin, shard.col_min)
        c_hi = jnp.maximum(q_cmax, shard.col_max)
    lo, hi = K.hoeffding_from_moments(mom, c_lo, c_hi, alpha=alpha)
    return r, m, hi - lo, tiles


# ----------------------------------------------------------------------------
# score stage (single-sourced in repro.core.scoring)
# ----------------------------------------------------------------------------

def score_stats(r, m, ci_len, scorer, floor, axis_names=None):
    """The §4.4 scoring tail shared by every plan: (r, m, ci_len) → scores,
    with the m ≥ floor eligibility gate (ineligible → −inf).

    The scorer formulas live in `repro.core.scoring` — `se_z_factor` (s2)
    and `ci_h_factor_from_bounds` (s4) — this function only supplies the
    distributed s4 normalisation bounds (pmin/pmax across shards when
    ``axis_names`` is given; min/max are exact, so any candidate subset
    containing every eligible candidate normalises identically — the
    ``prune='safe'`` equivalence, DESIGN.md §5) and the scorer *selection*:
    a traced operand from `request_operands` picks s1/s2/s4 with a bitwise
    `where`, so a per-request scorer flip costs no compile and changes no
    float of the chosen scorer's output.
    """
    eligible = m >= floor
    abs_r = jnp.abs(r)
    static = isinstance(scorer, str)
    if static and scorer == "s1":
        return jnp.where(eligible, abs_r, -jnp.inf)
    if static and scorer == "s2":
        return jnp.where(eligible, abs_r * SC.se_z_factor(m), -jnp.inf)
    if static and scorer != "s4":
        raise ValueError(f"unknown scorer {scorer!r}: use one of "
                         f"{FAST_SCORERS}")
    # s4: globally list-normalised Hoeffding CI factor, per query row
    lmin, lmax = SC.ci_h_bounds(ci_len, eligible, axis=-1)
    if axis_names:  # global normalisation across shards
        lmin = jax.lax.pmin(lmin, axis_names)
        lmax = jax.lax.pmax(lmax, axis_names)
    s4 = abs_r * SC.ci_h_factor_from_bounds(ci_len, lmin[..., None],
                                            lmax[..., None])
    if static:
        s = s4
    else:
        s = jnp.where(scorer < 0.5, abs_r,
                      jnp.where(scorer < 1.5, abs_r * SC.se_z_factor(m), s4))
    return jnp.where(eligible, s, -jnp.inf)


# ----------------------------------------------------------------------------
# rank stage
# ----------------------------------------------------------------------------

def _topk_gathered(s, r, m, gids, k, axes):
    """Rank stage: local top-k + cross-device combine — an all-gather of
    O(devices × k) bytes, independent of index size; ``gids`` must already
    be global index-space ids."""
    kk = min(k, s.shape[-1])
    top_s, top_i = jax.lax.top_k(s, kk)
    top_g = jnp.take_along_axis(jnp.broadcast_to(gids, s.shape), top_i,
                                axis=-1)
    cat = s.ndim - 1
    gather = lambda x: jax.lax.all_gather(x, axes, axis=cat, tiled=True)
    all_s = gather(top_s)
    all_g = gather(top_g)
    all_r = gather(jnp.take_along_axis(r, top_i, axis=-1))
    all_m = gather(jnp.take_along_axis(m, top_i, axis=-1))
    fs, fi = jax.lax.top_k(all_s, k)
    take = lambda x: jnp.take_along_axis(x, fi, axis=-1)
    return fs, take(all_g), take(all_r), take(all_m)


def _topk_local(s, r, m, gids, k):
    """Rank stage, ``combine='host'`` variant: each device emits only its
    local top-k (scores, global ids, r, m) — sharded ``[.., k]`` outputs
    that concatenate to ``[.., D·k]`` on the host, where
    `combine_local_topk` finishes the merge. Nothing crosses shards in
    program (the s4 pmin/pmax normalisation aside)."""
    kk = min(k, s.shape[-1])
    top_s, top_i = jax.lax.top_k(s, kk)
    top_g = jnp.take_along_axis(jnp.broadcast_to(gids, s.shape), top_i,
                                axis=-1)
    take = lambda x: jnp.take_along_axis(x, top_i, axis=-1)
    return top_s, top_g, take(r), take(m)


def combine_local_topk(s, g, r, m, k: int):
    """Host-side cross-shard rank combine for ``combine='host'`` plans:
    merge the concatenated per-device local top-k rows ``[.., D·kk]`` into
    the global top-k under the deterministic total order *score descending,
    global id ascending* — the same order the in-program gather combine and
    the `Server`'s cross-segment merge implement, so the result is
    bit-identical to the single-host rank stage."""
    s, g = np.asarray(s), np.asarray(g)
    pick = np.lexsort((g, -s), axis=-1)[..., :k]
    take = lambda x: np.take_along_axis(np.asarray(x), pick, axis=-1)
    return take(s), take(g), take(r), take(m)


def _rank_outputs(s, r, m, gids, k, axes, host_combine: bool, tiles):
    """Every plan's rank-stage outputs: the ``(s, g, r, m)`` top-k strips
    (`_topk_local` under the host combine, else `_topk_gathered`) and the
    rank kernel's ``[live, total]`` pair tiles summed over the mesh, which
    the host reads with the strips (`serve._SegmentExec._finish_ranked`)."""
    top = (_topk_local(s, r, m, gids, k) if host_combine
           else _topk_gathered(s, r, m, gids, k, axes))
    return top + (jax.lax.psum(tiles, axes),)


def _rank_out_specs(axes, batched: bool, host_combine: bool):
    """out_specs of the five `_rank_outputs`: the strips replicated for the
    gather combine, sharded along the (per-device) top-k axis for the host
    combine; the pair tiles replicated."""
    if not host_combine:
        return (P(),) * 5
    spec = P(None, axes) if batched else P(axes)
    return (spec,) * 4 + (P(),)


def _linear_device_index(axes, sizes):
    """Row-major linear device id over possibly-multiple mesh axes; the
    per-axis ``sizes`` are static (from the mesh), so this works on every
    jax version that has `axis_index`."""
    lin = jax.lax.axis_index(axes[0])
    for ax, size in zip(axes[1:], sizes[1:]):
        lin = lin * size + jax.lax.axis_index(ax)
    return lin


def _axis_sizes(mesh, axes):
    return tuple(int(mesh.shape[a]) for a in axes)


_QUERY_SPECS = (P(), P(), P(), P(), P())


def _shard_specs(axes):
    spec = P(axes)
    return IndexShard(key_hash=spec, values=spec, mask=spec,
                      col_min=spec, col_max=spec, rows=spec)


def _prep_specs(axes):
    spec = P(axes)
    return PreppedShard(dk=spec, sid=spec)


# ----------------------------------------------------------------------------
# prep builder (shared by every sortmerge plan)
# ----------------------------------------------------------------------------

def make_prep_fn(mesh, C_total: int, n: int, shape):
    """Build a jitted program that precomputes the per-shard candidate sort
    structure (`PreppedShard`, DESIGN.md §3) for the batched query path.
    Run it once per resident index + score_chunk; pass its result to any
    plan built with ``with_prep=True``. ``shape`` is anything with a
    ``score_chunk`` (a `ShapePolicy` or a legacy QueryConfig).
    """
    shape = resolve_kernels(shape, mesh)
    axes = tuple(mesh.axis_names)
    ndev = int(mesh.devices.size)
    assert C_total % ndev == 0
    score_chunk = int(shape.score_chunk)

    def local(shard: IndexShard):
        kh, mask = shard.key_hash, shard.mask
        C = kh.shape[0]
        chunk, pad, nb = _chunk_layout(C, score_chunk)
        if pad:
            kh = jnp.pad(kh, ((0, pad), (0, 0)), constant_values=_PAD_KEY)
            mask = jnp.pad(mask, ((0, pad), (0, 0)))
        resh = lambda a: a.reshape((nb, chunk) + a.shape[1:])
        dk, sid = jax.lax.map(lambda ab: _prep_block(*ab),
                              (resh(kh), resh(mask)))
        return PreppedShard(dk=dk.reshape(C + pad, n),
                            sid=sid.reshape(C + pad, n))

    fn = shard_map(local, mesh=mesh, in_specs=(_shard_specs(axes),),
                   out_specs=_prep_specs(axes),
                   check_vma=False)
    return jax.jit(fn)


# ----------------------------------------------------------------------------
# plan: scan — probe → score → rank, no filter stage
# ----------------------------------------------------------------------------

def make_scan_fn(mesh, C_total: int, n: int, shape: ShapePolicy,
                 batch: Optional[int] = None, with_prep: bool = False):
    """Build the jitted full-scan plan for a given index shape (paper
    Defn. 3 evaluated as the DESIGN.md §3 sharded scan): the pipeline with
    no filter stage.

    Signature: ``fn(q_kh, q_val, q_mask, q_cmin, q_cmax, shard[, prep],
    ops)`` where ``ops`` is the `request_operands` vector. ``batch=None``
    compiles the single-query program (query arrays ``[n]``, results
    ``[k_max]``); ``batch=B`` takes a leading ``[B]`` axis and returns
    ``[B, k_max]`` results bit-identical to B sequential calls, while
    scanning the index once per dispatch. One compiled instance serves
    every estimator × scorer × α × floor and any request k ≤ k_max.
    Returns `_rank_outputs`: the ranked strips and the rank kernel's
    ``[live, total]`` pair tiles.
    """
    shape = resolve_kernels(shape, mesh)
    axes = tuple(mesh.axis_names)
    sizes = _axis_sizes(mesh, axes)
    ndev = int(mesh.devices.size)
    assert C_total % ndev == 0
    assert not (with_prep and batch is None), "prep applies to the batched path"
    k = shape.k_max
    host_combine = _plan_combine(shape, ndev)

    def local(q_kh, q_val, q_mask, q_cmin, q_cmax, shard: IndexShard, *rest):
        if batch is not None:  # the advertised static batch size is binding
            assert q_kh.shape[0] == batch, (q_kh.shape, batch)
        else:
            assert q_kh.ndim == 1, q_kh.shape
        prep = rest[0] if with_prep else None
        est, scorer, alpha, floor = _unpack_ops(rest[-1])
        r, m, ci_len, tiles = _shard_stats(q_kh, q_val, q_mask, q_cmin,
                                           q_cmax, shard, shape, est, alpha,
                                           prep=prep)
        s = score_stats(r, m, ci_len, scorer, floor, axis_names=axes)
        Cl = s.shape[-1]
        lin = _linear_device_index(axes, sizes)
        gids = (jnp.arange(Cl, dtype=jnp.int32)
                + lin.astype(jnp.int32) * Cl)
        return _rank_outputs(s, r, m, gids, k, axes, host_combine, tiles)

    in_specs = _QUERY_SPECS + (_shard_specs(axes),)
    if with_prep:
        in_specs += (_prep_specs(axes),)
    in_specs += (P(),)   # the replicated request-operand vector
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=_rank_out_specs(axes, batch is not None,
                                             host_combine),
                   check_vma=False)  # gather outputs replicated, host sharded
    return jax.jit(fn)


# ----------------------------------------------------------------------------
# plan: probe — stage-1 containment scan (request-independent)
# ----------------------------------------------------------------------------

def _hits_block_single(qk_s, qm_s, kh, mask):
    """Hit counts of one candidate block against the pre-sorted query keys.

    The stage-1 twin of `_sortmerge_moments` with the query sort hoisted out
    of the chunk loop (the query table is block-invariant): one binary
    search per candidate slot, one reduction — no value traffic, no moment
    sums (DESIGN.md §5)."""
    PAD = _JPAD
    ck = jnp.where(mask > 0, kh, PAD)                               # [C, n]
    pos = jnp.clip(jnp.searchsorted(qk_s, ck.reshape(-1)),
                   0, qk_s.shape[0] - 1).reshape(ck.shape)
    hitc = (qk_s[pos] == ck) & (qm_s[pos] > 0) & (mask > 0)
    return jnp.sum(hitc.astype(jnp.float32), axis=-1)               # [C]


def _block_probes(q_kh, q_mask, dk):
    """Probe the whole query batch against one block's sorted distinct-key
    table ``dk [Mb]``. Returns ``flat [B·nq] i32``: the dk position of each
    hit, or the sentinel ``Mb + 1`` for misses (one past the dump column, so
    a size-``Mb+1`` scatter drops it as out-of-bounds). ``flat`` is the
    whole probe state — both stages' membership tables scatter from it,
    which is what lets stage 2 skip the binary search entirely."""
    Mb = dk.shape[0]
    PAD = _JPAD
    qk = jnp.where(q_mask > 0, q_kh, PAD).reshape(-1)
    pos = jnp.clip(jnp.searchsorted(dk, qk), 0, Mb - 1)
    hit = (dk[pos] == qk) & (q_mask.reshape(-1) > 0) & (qk != PAD)
    return jnp.where(hit, pos.astype(jnp.int32), jnp.int32(Mb + 1))


def _block_bits(flat, B: int, T: int):
    """Bit-packed membership table ``[T] u32``: bit b of slot t set iff
    query row b holds distinct key t. One u32 scatter-add builds it (keys
    are distinct within a row, so a bit is added at most once; misses index
    out of bounds and are dropped); downstream consumers pay one u32 gather
    for the whole batch instead of B float gathers — the memory-traffic
    trick that makes stage 1 cheap (DESIGN.md §5). Requires B ≤ 32."""
    nq = flat.shape[0] // B
    bit = jnp.left_shift(jnp.uint32(1),
                         jnp.repeat(jnp.arange(B, dtype=jnp.uint32), nq))
    return jnp.zeros((T,), jnp.uint32).at[flat].add(bit)


def _block_hittab(flat, B: int, T: int):
    """Per-row float membership table ``[B, T]`` — the B > 32 fallback for
    `_block_bits` (the exact structure `_sortmerge_moments_batched`
    scatters internally)."""
    nq = flat.shape[0] // B
    row = jnp.repeat(jnp.arange(B, dtype=jnp.int32), nq) * T
    vflat = jnp.where(flat < T, row + flat, B * T)
    return jnp.zeros((B * T,), jnp.float32).at[vflat].set(1.0).reshape(B, T)


def _block_vtab(flat, qv, B: int, T: int):
    """Per-row query-value table ``[B, T]``: the value of row b's key at
    distinct-key slot t (zero elsewhere). Scattered from the stage-1 probe
    state, so stage 2 never re-searches."""
    nq = flat.shape[0] // B
    row = jnp.repeat(jnp.arange(B, dtype=jnp.int32), nq) * T
    vflat = jnp.where(flat < T, row + flat, B * T)
    return jnp.zeros((B * T,), jnp.float32).at[vflat].set(qv).reshape(B, T)


def _w_from_bits(bits_g, B: int):
    """Expand gathered bit-packed membership (u32 ``[...]``) into per-row
    floats ``[B, ...]`` — B cheap vector ops replacing B float gathers."""
    return jnp.stack([((bits_g >> jnp.uint32(b)) & jnp.uint32(1))
                      .astype(jnp.float32) for b in range(B)])


def _use_bits(B: int) -> bool:
    return B <= 32


def _hits_block_tables(q_kh, q_mask, kh, mask, prep):
    """Stage-1 core for one candidate block (batched XLA sortmerge path):
    probe → membership table → per-candidate hit counts via the per-slot
    segment ids. Returns ``(hits [B, chunk], bits [T] u32, flat [B·nq])`` —
    the tables are handed to stage 2 so the probe work is paid once per
    dispatch, not once per stage (DESIGN.md §5).

    Exactness: a hit bit is set exactly for (row, distinct key) membership,
    and every valid candidate slot maps to its key's table slot (invalid
    slots → the never-written dump column), so the count equals the exact
    sketch intersection size — the scoring path's sample size ``m``."""
    B = q_kh.shape[0]
    if prep is None:
        dk, sid = _prep_block(kh, mask)
    else:
        dk, sid = prep
    Mb = dk.size
    T = Mb + 1
    flat = _block_probes(q_kh, q_mask, dk.reshape(-1))
    if _use_bits(B):
        bits = _block_bits(flat, B, T)
        bg = jnp.take(bits, sid.reshape(-1)).reshape(kh.shape)     # [chunk, n]
        hits = _w_from_bits(bg, B).sum(-1)
    else:
        bits = jnp.zeros((T,), jnp.uint32)      # stage 2 rebuilds from flat
        tab = _block_hittab(flat, B, T)
        w = jnp.take(tab, sid.reshape(-1), axis=-1).reshape(
            (B,) + kh.shape)
        hits = w.sum(-1)
    return hits, bits, flat


def _shard_hits(q_kh, q_mask, shard: IndexShard, shape: ShapePolicy,
                prep: Optional[PreppedShard] = None,
                emit_tables: bool = False):
    """Stage-1 scan: exact sketch-intersection sizes for every candidate in
    a shard, chunked exactly like `_shard_stats` (same ``score_chunk``
    blocks, so the precomputed `PreppedShard` is shared between stages).
    Returns hits ``[..., C]`` — by key-distinctness this *is* the
    sketch-join sample size ``m`` the scoring path would compute, which is
    what makes ``prune='safe'`` correctness-preserving (DESIGN.md §5).

    ``emit_tables`` (batched XLA-sortmerge only) additionally returns the
    per-block probe state ``(bits [nb, T], flat [nb, B·nq])`` for the
    stage-2 program to reuse."""
    batched = q_kh.ndim == 2
    C = shard.key_hash.shape[0]
    chunk, pad, nb = _chunk_layout(C, shape.score_chunk)
    kh, mask = shard.key_hash, shard.mask
    if pad:
        kh = jnp.pad(kh, ((0, pad), (0, 0)), constant_values=_PAD_KEY)
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    Cp = C + pad
    if prep is not None:
        assert prep.dk.shape[0] == Cp, (prep.dk.shape, Cp)

    sortmerge = uses_sortmerge(shape)
    assert not emit_tables or (batched and sortmerge), \
        "probe tables exist only on the batched sortmerge path"
    if sortmerge and not batched:
        PAD = _JPAD
        q_eff = jnp.where(q_kh != PAD, q_mask, 0.0)
        qk = jnp.where(q_eff > 0, q_kh, PAD)
        order = jnp.argsort(qk)
        qk_s = qk[order]
        qm_s = q_eff[order]
        block = lambda ckh, cmask, cprep: _hits_block_single(
            qk_s, qm_s, ckh, cmask)
    elif sortmerge:
        block = lambda ckh, cmask, cprep: _hits_block_tables(
            q_kh, q_mask, ckh, cmask, cprep)
    elif batched:
        block = lambda ckh, cmask, cprep: K.containment_hits_batched(
            q_kh, q_mask, ckh, cmask, shape.kernels)
    else:
        block = lambda ckh, cmask, cprep: K.containment_hits(
            q_kh, q_mask, ckh, cmask, shape.kernels)

    have_prep = prep is not None and sortmerge and batched
    tables = sortmerge and batched
    if nb > 1:
        resh = lambda a: a.reshape((nb, chunk) + a.shape[1:])
        blocks_prep = ((resh(prep.dk), resh(prep.sid)) if have_prep
                       else (jnp.zeros((nb, 0)), jnp.zeros((nb, 0))))

        def one(args):
            ckh, cmask, cdk, csid = args
            return block(ckh, cmask, (cdk, csid) if have_prep else None)

        out = jax.lax.map(one, (resh(kh), resh(mask), *blocks_prep))
        hits = out[0] if tables else out
        # lax.map stacks the chunk axis in front: [nb, ..., chunk] → [..., Cp]
        hits = jnp.moveaxis(hits, 0, -2).reshape(q_kh.shape[:-1] + (Cp,))
        hits = hits[..., :C]
        if emit_tables:
            return hits, out[1], out[2]
        return hits
    out = block(kh, mask, (prep.dk, prep.sid) if have_prep else None)
    hits = (out[0] if tables else out)[..., :C]
    if emit_tables:
        return hits, out[1][None], out[2][None]
    return hits


def make_probe_fn(mesh, C_total: int, n: int, shape: ShapePolicy,
                  batch: Optional[int] = None, with_prep: bool = False,
                  emit_tables: bool = False):
    """Build the jitted stage-1 containment-scan plan (DESIGN.md §5):
    query arrays + sharded index → per-candidate hit counts ``[.., C_total]``
    (sharded along the candidate axis, gathered to the host by the caller).

    This plan is **request-independent** — hit counts are pure set algebra
    over the key planes — so it takes no operand vector; one compiled
    instance serves every request. The hit counts are *exact* (not
    estimates), see `_shard_hits`; turning them into containment/Jaccard/
    join-size estimates is host-side math (`repro.core.containment`).

    ``emit_tables`` makes the program also return the device-resident probe
    state ``(bits [nb·ndev, T] u32, flat [nb·ndev, B·n_q] i32)`` that
    `make_pruned_fn` consumes — the binary searches and membership scatters
    of a dispatch are then paid exactly once across both stages."""
    shape = resolve_kernels(shape, mesh)
    axes = tuple(mesh.axis_names)
    ndev = int(mesh.devices.size)
    assert C_total % ndev == 0
    assert not (with_prep and batch is None), "prep applies to the batched path"
    assert not emit_tables or batch is not None

    def local(q_kh, q_val, q_mask, q_cmin, q_cmax, shard: IndexShard, *rest):
        if batch is not None:
            assert q_kh.shape[0] == batch, (q_kh.shape, batch)
        else:
            assert q_kh.ndim == 1, q_kh.shape
        return _shard_hits(q_kh, q_mask, shard, shape,
                           prep=rest[0] if rest else None,
                           emit_tables=emit_tables)

    in_specs = _QUERY_SPECS + (_shard_specs(axes),)
    if with_prep:
        in_specs += (_prep_specs(axes),)
    hits_spec = P(axes) if batch is None else P(None, axes)
    out_specs = ((hits_spec, P(axes), P(axes)) if emit_tables else hits_spec)
    fn = shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return jax.jit(fn)


# ----------------------------------------------------------------------------
# gather + score stages (pruned plans)
# ----------------------------------------------------------------------------

def _gathered_stats(a, w, values_g, cmin_g, cmax_g, q_cmin, q_cmax,
                    shape: ShapePolicy, est, alpha):
    """(aligned query values, membership, gathered candidate side) → per-
    candidate (r, m, ci_len) and the rank kernel's pair tiles, mirroring
    `_score_block` + `_shard_stats` arithmetic: every per-slot float is the same untouched value the full
    scan would see, and ``m`` (integer-valued sums of {0,1}) is exactly
    equal. Real-valued scores agree to within a few ulps — XLA may order
    the slot reductions differently across program shapes."""
    b = values_g * w
    mom = jnp.stack([w.sum(-1), a.sum(-1), b.sum(-1), (a * a).sum(-1),
                     (b * b).sum(-1), (a * b).sum(-1)], -1)

    r, tiles = _est_select(
        est, lambda: (K.pearson_from_moments(mom), _no_tiles()),
        lambda: _ranked(a, b, w, "spearman", shape),
        lambda: _ranked(a, b, w, "rin", shape),
        lambda: (K.qn_correlation(a, b, w, shape.kernels), _no_tiles()))
    m = mom[..., 0]
    c_lo = jnp.minimum(q_cmin[..., None], cmin_g)
    c_hi = jnp.maximum(q_cmax[..., None], cmax_g)
    lo, hi = K.hoeffding_from_moments(mom, c_lo, c_hi, alpha=alpha)
    return r, m, hi - lo, tiles


def _survivor_stats(q_kh, q_val, q_mask, q_cmin, q_cmax, shard: IndexShard,
                    surv, valid, lin, C_local: int, shape: ShapePolicy,
                    est, alpha):
    """Generic stage-2 body: gather the survivor rows this device owns into
    a masked sub-shard and run the ordinary chunked scorer on it → per-
    survivor (r, m, ci_len), each ``[.., M]``, and the rank kernel's pair
    tiles. Shared by the host-selected `make_pruned_fn` path and the fused
    inverted plan (`make_inverted_fn`) —
    identical survivor inputs therefore produce bit-identical stats. Rows
    owned by other devices (and padding beyond ``valid``) stay fully masked:
    they score −inf and the rank combine drops them."""
    loc = surv.astype(jnp.int32) - lin.astype(jnp.int32) * C_local
    ok = valid & (loc >= 0) & (loc < C_local)
    locc = jnp.clip(loc, 0, C_local - 1)
    okf = ok.astype(jnp.float32)
    sub = IndexShard(
        key_hash=jnp.where(ok[:, None], shard.key_hash[locc], _PAD_KEY),
        values=shard.values[locc] * okf[:, None],
        mask=shard.mask[locc] * okf[:, None],
        col_min=jnp.where(ok, shard.col_min[locc], 0.0),
        col_max=jnp.where(ok, shard.col_max[locc], 0.0),
        rows=shard.rows[locc] * okf)
    return _shard_stats(q_kh, q_val, q_mask, q_cmin, q_cmax, sub, shape,
                        est, alpha, prep=None)


def make_pruned_fn(mesh, C_total: int, n: int, shape: ShapePolicy, M: int,
                   batch: Optional[int] = None, with_prep: bool = False):
    """Build the jitted gather + score + rank plan: score only ``M``
    gather-compacted survivor columns of a ``C_total``-column index
    (the filter stage ran on the host, DESIGN.md §5).

    Signature: ``fn(q_kh, q_val, q_mask, q_cmin, q_cmax, shard, surv,
    valid[, bits, flat, prep], ops)`` — ``surv [M]`` holds global survivor
    column ids (tail padded; ``valid [M]`` false there); ``bits``/``flat``
    are the probe tables emitted by ``make_probe_fn(..., emit_tables=True)``
    for the *same* query batch, so this program re-does no binary search and
    no membership scatter except the per-row value table. Everything runs on
    device against the resident index — the host ships only the id vector.
    Each device gathers the survivor rows it owns (others stay masked →
    −inf → dropped by the cross-device top-k combine) and returns the usual
    (scores, gids, r, m) with **gids already in index space**, plus the
    rank kernel's pair tiles (`_rank_outputs`).

    ``M`` must come from the fixed ladder ``prune_base · 2^i`` (see
    `prune_rung`) so the compile cache stays O(log C); ``M ≥ k_max``
    required.
    """
    shape = resolve_kernels(shape, mesh)
    axes = tuple(mesh.axis_names)
    sizes = _axis_sizes(mesh, axes)
    ndev = int(mesh.devices.size)
    assert C_total % ndev == 0
    C_local = C_total // ndev
    assert shape.k_max <= M, (shape.k_max, M)
    assert not (with_prep and batch is None), "prep applies to the batched path"
    k = shape.k_max
    host_combine = _plan_combine(shape, ndev)
    chunk, _, nb = _chunk_layout(C_local, shape.score_chunk)
    T = chunk * n + 1

    def local(q_kh, q_val, q_mask, q_cmin, q_cmax, shard: IndexShard,
              surv, valid, *rest):
        if batch is not None:
            assert q_kh.shape[0] == batch, (q_kh.shape, batch)
        else:
            assert q_kh.ndim == 1, q_kh.shape
        est, scorer, alpha, floor = _unpack_ops(rest[-1])
        lin = _linear_device_index(axes, sizes)
        loc = surv.astype(jnp.int32) - lin.astype(jnp.int32) * C_local
        ok = valid & (loc >= 0) & (loc < C_local)
        locc = jnp.clip(loc, 0, C_local - 1)
        okf = ok.astype(jnp.float32)
        batched = q_kh.ndim == 2

        if with_prep and batched:
            bits, flat, prep = rest[:3]
            B = q_kh.shape[0]
            qv = (q_val * q_mask).reshape(-1)
            vtab = jax.lax.map(lambda f: _block_vtab(f, qv, B, T), flat)
            vtab = jnp.moveaxis(vtab, 0, 1).reshape(B, nb * T)   # [B, nb·T]
            if _use_bits(B):
                wtab = None
                bits_flat = bits.reshape(-1)                     # [nb·T]
            else:
                wtab = jax.lax.map(lambda f: _block_hittab(f, B, T), flat)
                wtab = jnp.moveaxis(wtab, 0, 1).reshape(B, nb * T)
            sid_g = jnp.where(ok[:, None], prep.sid[locc], chunk * n)
            blk = jnp.clip(locc // chunk, 0, nb - 1)
            gidx = blk[:, None] * T + sid_g                      # [M, n]
            values_g = shard.values[locc] * okf[:, None]
            cmin_g = jnp.where(ok, shard.col_min[locc], 0.0)
            cmax_g = jnp.where(ok, shard.col_max[locc], 0.0)

            # stream survivors in score_chunk blocks — bounds the [B, ·, n]
            # aligned-value tensors exactly like the full scan's streaming;
            # the s4 normalisation runs once over all M below
            cs = min(shape.score_chunk, M)
            mpad = (-M) % cs
            mb = (M + mpad) // cs
            padb = lambda x: (jnp.pad(x, ((0, mpad),) + ((0, 0),) *
                                      (x.ndim - 1)) if mpad else x)

            def one(args):
                gi, vg, cl, ch = args
                a = jnp.take(vtab, gi.reshape(-1), axis=-1).reshape(B, cs, n)
                if _use_bits(B):
                    bg = jnp.take(bits_flat, gi.reshape(-1)).reshape(cs, n)
                    w = _w_from_bits(bg, B)
                else:
                    w = jnp.take(wtab, gi.reshape(-1),
                                 axis=-1).reshape(B, cs, n)
                return _gathered_stats(a, w, vg[None], cl[None], ch[None],
                                       q_cmin, q_cmax, shape, est, alpha)

            if mb > 1:
                blocks = (padb(gidx).reshape(mb, cs, n),
                          padb(values_g).reshape(mb, cs, n),
                          padb(cmin_g).reshape(mb, cs),
                          padb(cmax_g).reshape(mb, cs))
                r, m, ci_len, tiles = jax.lax.map(one, blocks)
                mv = lambda x: jnp.moveaxis(x, 0, -2).reshape(
                    (B, M + mpad))[..., :M]
                r, m, ci_len, tiles = mv(r), mv(m), mv(ci_len), tiles.sum(0)
            else:
                r, m, ci_len, tiles = one((gidx, values_g, cmin_g, cmax_g))
        else:
            # generic path (single-query / eq-matrix / Pallas backends):
            # gather the survivor sub-shard and run the ordinary scorer on
            # it (the loc/ok recompute inside folds away under CSE)
            r, m, ci_len, tiles = _survivor_stats(
                q_kh, q_val, q_mask, q_cmin, q_cmax, shard, surv, valid,
                lin, C_local, shape, est, alpha)
        s = score_stats(r, m, ci_len, scorer, floor, axis_names=axes)
        return _rank_outputs(s, r, m, surv.astype(jnp.int32), k, axes,
                             host_combine, tiles)

    in_specs = _QUERY_SPECS + (_shard_specs(axes), P(), P())
    if with_prep:
        in_specs += (P(axes), P(axes), _prep_specs(axes))
    in_specs += (P(),)
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=_rank_out_specs(axes, batch is not None,
                                             host_combine),
                   check_vma=False)  # gather outputs replicated, host sharded
    return jax.jit(fn)


def topm_width(shape: ShapePolicy, C_local: int) -> int:
    """Candidates per query row and device shard that the fused ``topm``
    plan scores: ``prune_m``, never under ``k_max``, never over the shard."""
    return max(min(int(shape.prune_m), C_local), min(shape.k_max, C_local))


def make_topm_fn(mesh, C_total: int, n: int, shape: ShapePolicy, batch: int,
                 with_prep: bool = False):
    """Build the fused ``prune='topm'`` plan: probe, per-row top-M filter,
    gather and score in **one dispatch** (DESIGN.md §5) — no host
    round-trip, because the survivor count is the static
    ``shape.prune_m`` per device.

    Semantics: each query row keeps its own M best candidates *per device
    shard* by exact intersection size (ties → lower id, `lax.top_k`), so
    the final result is the top-k over the union of per-shard top-Ms. A
    candidate outside a row's top-M is not scored for that row — with
    ``prune_m ≥`` the row's eligible-candidate count this is every candidate
    that could score at all, and results match the full scan; smaller
    ``prune_m`` trades recall for latency (the s4 list-normalisation then
    spans the row's survivor list, like a per-segment list in
    `repro.engine.lifecycle`)."""
    shape = resolve_kernels(shape, mesh)
    axes = tuple(mesh.axis_names)
    sizes = _axis_sizes(mesh, axes)
    ndev = int(mesh.devices.size)
    assert C_total % ndev == 0
    C_local = C_total // ndev
    k = shape.k_max
    host_combine = _plan_combine(shape, ndev)
    M = topm_width(shape, C_local)
    chunk, _, nb = _chunk_layout(C_local, shape.score_chunk)
    T = chunk * n + 1
    B = int(batch)

    def local(q_kh, q_val, q_mask, q_cmin, q_cmax, shard: IndexShard, *rest):
        assert q_kh.shape[0] == B, (q_kh.shape, B)
        lin = _linear_device_index(axes, sizes)
        prep = rest[0] if with_prep else None
        est, scorer, alpha, floor = _unpack_ops(rest[-1])

        if with_prep:
            hits, bits, flat = _shard_hits(q_kh, q_mask, shard, shape,
                                           prep=prep, emit_tables=True)
        else:
            hits = _shard_hits(q_kh, q_mask, shard, shape, prep=prep)
        hits = jnp.where(hits >= floor, hits, -1.0)
        _, ids = jax.lax.top_k(hits, M)                           # [B, M]

        if with_prep:
            qv = (q_val * q_mask).reshape(-1)
            vtab = jax.lax.map(lambda f: _block_vtab(f, qv, B, T), flat)
            vtab = jnp.moveaxis(vtab, 0, 1).reshape(B, nb * T)
            sid_g = prep.sid[ids]                                 # [B, M, n]
            blk = jnp.clip(ids // chunk, 0, nb - 1)
            gidx = (blk[..., None] * T + sid_g).reshape(B, M * n)
            a = jnp.take_along_axis(vtab, gidx, axis=-1).reshape(B, M, n)
            if _use_bits(B):
                bg = jnp.take(bits.reshape(-1), gidx)             # [B, M·n]
                w = jnp.stack([((bg[b] >> jnp.uint32(b)) & jnp.uint32(1))
                               .astype(jnp.float32) for b in range(B)])
                w = w.reshape(B, M, n)
            else:
                wtab = jax.lax.map(lambda f: _block_hittab(f, B, T), flat)
                wtab = jnp.moveaxis(wtab, 0, 1).reshape(B, nb * T)
                w = jnp.take_along_axis(wtab, gidx, axis=-1).reshape(B, M, n)
            take_rows = lambda x: jnp.take(x, ids.reshape(-1),
                                           axis=0).reshape((B, M) +
                                                           x.shape[1:])
            values_g = take_rows(shard.values)
            cmin_g = take_rows(shard.col_min)
            cmax_g = take_rows(shard.col_max)
            r, m, ci_len, tiles = _gathered_stats(a, w, values_g, cmin_g,
                                                  cmax_g, q_cmin, q_cmax,
                                                  shape, est, alpha)
        else:
            # per-row candidate sets: score each row's gathered sub-sketches
            # with the single-query kernels (vmapped over the batch)
            take_rows = lambda x: jnp.take(x, ids.reshape(-1),
                                           axis=0).reshape((B, M) +
                                                           x.shape[1:])
            ckh = take_rows(shard.key_hash)
            cvals = take_rows(shard.values)
            cmask = take_rows(shard.mask)
            mom, r, tiles = jax.vmap(
                lambda qk1, qv1, qm1, a1, b1, c1: _score_block(
                    qk1, qv1, qm1, a1, b1, c1, shape, est))(
                        q_kh, q_val, q_mask, ckh, cvals, cmask)
            tiles = tiles.sum(0)
            m = mom[..., 0]
            c_lo = jnp.minimum(q_cmin[:, None], take_rows(shard.col_min))
            c_hi = jnp.maximum(q_cmax[:, None], take_rows(shard.col_max))
            lo, hi = K.hoeffding_from_moments(mom, c_lo, c_hi, alpha=alpha)
            ci_len = hi - lo
        s = score_stats(r, m, ci_len, scorer, floor, axis_names=axes)
        gids = ids.astype(jnp.int32) + lin.astype(jnp.int32) * C_local
        return _rank_outputs(s, r, m, gids, k, axes, host_combine, tiles)

    in_specs = _QUERY_SPECS + (_shard_specs(axes),)
    if with_prep:
        in_specs += (_prep_specs(axes),)
    in_specs += (P(),)
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=_rank_out_specs(axes, True, host_combine),
                   check_vma=False)
    return jax.jit(fn)


# ----------------------------------------------------------------------------
# plan: inverted — fused postings probe → select → gather → score → rank
# ----------------------------------------------------------------------------

def _postings_window_candidates(q_kh, q_mask, keys, cols, E: int, W: int):
    """Shared front half of the inverted probe (DESIGN.md §7): per query
    key, ``searchsorted`` into the key-sorted postings planes and gather a
    W-wide window, emitting matched column ids ``cand i32[B, n·W]`` (−1 in
    non-matching slots) ready for `ops.postings_merge`. Single source for
    the standalone probe program (`repro.engine.candidates.
    make_postings_probe_fn`) and the fused plan below."""
    pos = jnp.searchsorted(keys, q_kh)              # [B, n]
    win = pos[..., None] + jnp.arange(W, dtype=pos.dtype)   # [B, n, W]
    ok = win < E
    win = jnp.minimum(win, E - 1)
    k_g = keys[win]
    c_g = cols[win]
    # PAD query slots are masked out; real keys never equal PAD (the
    # sentinel_safe reservation), so the PAD-padded tail cannot match
    match = ok & (k_g == q_kh[..., None]) & (c_g >= 0) \
        & (q_mask[..., None] > 0)
    return jnp.where(match, c_g, -1).reshape(q_kh.shape[0],
                                             q_kh.shape[1] * W)


def make_inverted_fn(mesh, C_total: int, n: int, shape: ShapePolicy, M: int,
                     E: int, W: int, batch: int):
    """Build the fused device-resident inverted plan (DESIGN.md §11):
    postings probe → merge → survivor select → gather → score → rank in
    **one dispatch** — no ``[B, C]`` materialisation, no mid-query host
    sync, no O(C) work anywhere.

    Signature: ``fn(q_kh, q_val, q_mask, q_cmin, q_cmax, shard, keys, cols,
    ops)`` with the postings planes ``keys u32[E]`` / ``cols i32[E]``
    replicated. Returns the usual `_rank_outputs` ``(s, g, r, m, tiles)``
    plus the replicated exact survivor-union count ``n_surv i32[]`` — the
    caller
    compares it against the static rung ``M`` to detect overflow and
    re-dispatch on the covering rung
    (`serve._SegmentExec._dispatch_safe_fused`; by `ops.postings_select`,
    ``n_surv`` is M-independent, so the covering rung is exact).

    The on-device select emits the ``prune='safe'`` survivor union
    ascending and zero-padded — the very layout the host builds from
    `select_survivors` — so the downstream `_survivor_stats` gather sees
    inputs identical to the host-selected `make_pruned_fn` path: identical
    survivor sets and ``m`` exactly, scores equal at equal rung M (and to
    within reduction-order ulps across rungs, as documented on
    `_gathered_stats`). ``M`` must come from the ``prune_base · 2^i``
    ladder and (E, W) from their own ladders (`lifecycle.ladder_rung`,
    `candidates.window_rung`), keeping compiled fused programs O(log)
    under index mutation.
    """
    shape = resolve_kernels(shape, mesh)
    axes = tuple(mesh.axis_names)
    sizes = _axis_sizes(mesh, axes)
    ndev = int(mesh.devices.size)
    assert C_total % ndev == 0
    C_local = C_total // ndev
    assert shape.k_max <= M, (shape.k_max, M)
    k = shape.k_max
    host_combine = _plan_combine(shape, ndev)
    B = int(batch)

    def local(q_kh, q_val, q_mask, q_cmin, q_cmax, shard: IndexShard,
              keys, cols, ops):
        assert q_kh.shape[0] == B, (q_kh.shape, B)
        est, scorer, alpha, floor = _unpack_ops(ops)
        cand = _postings_window_candidates(q_kh, q_mask, keys, cols, E, W)
        mcols, mcnt = K.postings_merge(cand, shape.kernels)
        surv, valid, n_surv = K.postings_select(mcols, mcnt, floor, M,
                                                shape.kernels)
        lin = _linear_device_index(axes, sizes)
        r, m, ci_len, tiles = _survivor_stats(
            q_kh, q_val, q_mask, q_cmin, q_cmax, shard, surv, valid, lin,
            C_local, shape, est, alpha)
        s = score_stats(r, m, ci_len, scorer, floor, axis_names=axes)
        # probe inputs are replicated, so n_surv is identical on every device
        return _rank_outputs(s, r, m, surv, k, axes, host_combine,
                             tiles) + (n_surv,)

    in_specs = _QUERY_SPECS + (_shard_specs(axes), P(), P(), P())
    out_specs = _rank_out_specs(axes, True, host_combine) + (P(),)
    fn = shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return jax.jit(fn)


# ----------------------------------------------------------------------------
# filter stage (host side) + the survivor-capacity ladder
# ----------------------------------------------------------------------------

def select_survivors(hits, prune: str, min_sample: int = 3,
                     prune_m: int = 128) -> np.ndarray:
    """Host-side stage-1 → stage-2 candidate selection — the filter stage of
    the ``prune`` plan (DESIGN.md §5).

    ``hits`` is ``[C]`` or ``[B, C]`` (a batch prunes to the *union* of its
    rows' survivor sets — a non-survivor stays ineligible for the rows that
    did not pick it, so per-row results are unaffected). Returns the sorted
    survivor ids:

    * ``prune='safe'`` — every candidate with ``hits ≥ min_sample`` for any
      row. Candidates below the floor score −inf in the full scan
      (`score_stats` eligibility, the §4.3 Hoeffding floor via
      `repro.core.bounds.hoeffding_eligibility_floor`), so this never drops
      a true top-k column;
    * ``prune='topm'`` — per row, the ``prune_m`` eligible candidates with
      the most hits (deterministic: stable sort, lower id wins ties). The
      host-side reference of the fused on-device selection in
      `make_topm_fn`.
    """
    h = np.atleast_2d(np.asarray(hits))
    eligible = h >= hoeffding_eligibility_floor(min_sample)
    if prune == "safe":
        return np.nonzero(eligible.any(0))[0].astype(np.int32)
    if prune == "topm":
        m = max(int(prune_m), 1)
        keep = np.zeros(h.shape[1], bool)
        for row, okr in zip(h, eligible):
            ids = np.argsort(-row, kind="stable")[:m]
            keep[ids[okr[ids]]] = True
        return np.nonzero(keep)[0].astype(np.int32)
    raise ValueError(f"unknown prune mode {prune!r}: use 'safe' or 'topm'")


def prune_rung(n_survivors: int, base: int, C_padded: int,
               ndev: int) -> Optional[int]:
    """Smallest device-aligned rung of the ladder ``base · 2^i`` holding the
    survivor set, or ``None`` when the rung would not beat the full scan
    (≥ the padded index width) — the caller then falls back to the already
    compiled full program. The fixed ladder keeps pruned dispatch shapes —
    and therefore compiled stage-2 programs — logarithmic in C
    (DESIGN.md §4)."""
    r = max(int(base), 1)
    while r < max(n_survivors, 1):
        r *= 2
    r += (-r) % ndev
    return None if r >= C_padded else r
