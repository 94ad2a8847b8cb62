"""FedAP — layer-adaptive structured pruning (paper Section 3.4, Algorithm 3).

Pipeline (executed ONCE, on the server, at a predefined round):

  1. Every participant k (server = 0) derives an *expected pruning rate*
     p*_k from the eigen-gap of a loss-curvature spectrum (the IMC /
     inertial-manifold criterion [62]): sort eigenvalues ascending and take
     the largest prefix m_k with  lambda_{m+1} - lambda_m > 4 * L_k, then
     p*_k = m_k / d_k.

     Hardware adaptation: the exact Hessian is not computable at any of the
     assigned scales, so the spectrum is the *empirical Fisher* spectrum
     obtained via the Gram trick — eigenvalues of (1/n) G G^T where G is the
     [n_probe, P] per-sample gradient matrix; G G^T is [n_probe, n_probe]
     and shares all nonzero eigenvalues with the Fisher (1/n) G^T G.

  2. Rates are aggregated with non-IID-degree weights (Formula 15):
         p* = sum_k [ (n_k / (D(P_k)+eps)) / sum_k' (...) ] * p*_k

  3. A global magnitude threshold V = |v_(floor(R * p*))| (the R*p*-th
     smallest |weight| over ALL prunable weights) converts p* into a
     per-layer rate p*_l = #{|w| < V in layer l} / q_l  (Alg. 3 lines 6-11).

  4. Within each layer, filters with the lowest HRank feature-map rank
     (computed on server data) are removed; we keep the top
     d_l - floor(p*_l * d_l) filters (lines 12-15).

Structured pruning is expressed model-agnostically through a
``PruneSpec``: each prunable layer names its weight tensor, the filter
axis, and every coupled tensor/axis that must shrink with it (bias, the
next layer's input axis, norm scales).  Models publish their own spec.

TPU note: kept-filter counts can optionally be rounded UP to a multiple of
128 (MXU lane width) so the shrunken matmuls stay hardware-aligned; this
only ever prunes *less* than p*_l, preserving the paper's p_l <= p*_l
inequality (Alg. 3 line 14).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Path = tuple


# ---------------------------------------------------------------------------
# Pytree path addressing — jax.tree_util key-paths, so PruneSpec works on ANY
# registered pytree (dicts, lists/tuples, namedtuples, registered dataclasses)
# ---------------------------------------------------------------------------

def _norm_key(entry) -> Any:
    """Normalize a jax.tree_util key entry to the plain key a PruneSpec
    path uses: dict key, sequence index, or attribute name."""
    jtu = jax.tree_util
    if isinstance(entry, jtu.DictKey):
        return entry.key
    if isinstance(entry, jtu.SequenceKey):
        return entry.idx
    if isinstance(entry, jtu.GetAttrKey):
        return entry.name
    if isinstance(entry, jtu.FlattenedIndexKey):
        return entry.key
    return entry


def get_path(tree: Any, path: Path):
    """The leaf at ``path``, resolved through tree_flatten_with_path."""
    path = tuple(path)
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if tuple(_norm_key(e) for e in kp) == path:
            return leaf
    raise KeyError(f"no leaf at path {path!r}")


def set_path(tree: Any, path: Path, value: Any):
    """Functional leaf replacement on any registered pytree."""
    path = tuple(path)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves, hit = [], False
    for kp, leaf in flat:
        if tuple(_norm_key(e) for e in kp) == path:
            leaves.append(value)
            hit = True
        else:
            leaves.append(leaf)
    if not hit:
        raise KeyError(f"no leaf at path {path!r}")
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Prune spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CoupledParam:
    path: Path
    axis: int


@dataclasses.dataclass(frozen=True)
class PrunableLayer:
    """One structurally-prunable layer.

    weight:      the tensor holding the filters (conv kernel [kh,kw,cin,cout],
                 FFN up-proj [d_model, d_ff], ...).
    filter_axis: the output-filter axis of ``weight``.
    coupled:     tensors that must be sliced along the same filter dimension
                 (bias of this layer; NEXT layer's input axis; norms).
    feature_key: key under which the model reports this layer's feature maps.
    """

    name: str
    weight: Path
    filter_axis: int
    coupled: tuple[CoupledParam, ...] = ()
    feature_key: str | None = None


@dataclasses.dataclass(frozen=True)
class PruneSpec:
    layers: tuple[PrunableLayer, ...]


# ---------------------------------------------------------------------------
# Step 1 — expected pruning rate from curvature spectrum (IMC criterion)
# ---------------------------------------------------------------------------

def fisher_spectrum(
    per_sample_grad_fn: Callable[[Any, Any], Any],
    params: Any,
    probe_batch: Any,
    *,
    n_valid: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Empirical-Fisher eigenvalues via the Gram trick.

    ``per_sample_grad_fn(params, batch) -> pytree with leading axis n`` must
    return per-sample gradients (e.g. ``jax.vmap(jax.grad(loss_one))``).
    Returns eigenvalues sorted ASCENDING (paper convention).

    ``n_valid`` supports PADDED probe batches (the sharded ragged-probe
    path): the Gram normalizer becomes ``n_valid`` instead of the row
    count, and — provided ``per_sample_grad_fn`` zeroes the padded rows —
    the padded Gram's spectrum is exactly the valid-row spectrum plus
    ``n - n_valid`` zero eigenvalues (zero rows/columns), which
    :func:`expected_rate_from_spectrum` masks out via its ``valid=``
    argument.

    The Gram matrix is accumulated leaf by leaf (``sum_leaf g g^T``), so
    no concatenated [n, P] copy of the per-sample gradients is ever
    materialized — at published LM widths that copy alone would be
    n x P x 4 bytes on top of the gradients themselves.
    """
    leaves = [x.reshape(x.shape[0], -1)
              for x in jax.tree.leaves(per_sample_grad_fn(params, probe_batch))]
    rows = leaves[0].shape[0]
    gram = jnp.zeros((rows, rows), jnp.float32)
    for x in leaves:
        x = x.astype(jnp.float32)
        gram = gram + x @ x.T
    n = rows if n_valid is None else n_valid
    gram = gram / n                               # [n, n], same nonzero spectrum
    eigs = jnp.linalg.eigvalsh(gram)              # ascending
    return jnp.clip(eigs, 0.0, None)


def lipschitz_estimate(
    grad_fn: Callable[[Any, Any], Any],
    params_a: Any,
    params_b: Any,
    batch: Any,
) -> jnp.ndarray:
    """L_k ~= ||grad(a) - grad(b)|| / ||a - b||  — finite-difference estimate
    of the Lipschitz constant of the base function B_k (Section 3.4)."""
    ga, gb = grad_fn(params_a, batch), grad_fn(params_b, batch)
    num = jnp.sqrt(sum(jnp.sum(jnp.square(x - y)) for x, y in
                       zip(jax.tree.leaves(ga), jax.tree.leaves(gb))))
    den = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
                       for x, y in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b))))
    return num / jnp.clip(den, 1e-12, None)


def expected_rate_from_spectrum(eigs: jnp.ndarray, lipschitz: jnp.ndarray,
                                max_rate: float = 0.9, *,
                                valid: jnp.ndarray | int | None = None
                                ) -> jnp.ndarray:
    """p*_k = m_k / d_k where m_k is the FIRST index (ascending order) with
    eig[m_k+1] - eig[m_k] > 4 L — the paper's Section 3.4 criterion: the
    modes below the first spectral gap form the prunable complement of the
    inertial manifold [62].

    ``valid`` restricts the search to a PADDED spectrum's valid tail (the
    sharded ragged-probe path): after clipping at 0, the ascending padded
    spectrum is value-for-value ``[0]*(len(eigs)-valid) + sorted(valid
    spectrum)``, so the eigen-gap search over its last ``valid`` entries —
    with indices re-based and the pad|valid boundary gap excluded — is
    exactly the search the host path runs on the unpadded spectrum.

    If no gap clears the bar, p*_k = 0 (prune nothing — safe default).
    """
    d_pad = eigs.shape[0]
    d = jnp.asarray(d_pad if valid is None else valid, jnp.int32)
    gaps = eigs[1:] - eigs[:-1]                      # [d_pad-1]
    # index of each gap within the valid tail; <= 0 means padding or the
    # pad|valid boundary, which the host path's spectrum has no gap for
    idx = jnp.arange(1, d_pad, dtype=jnp.int32) - (jnp.int32(d_pad) - d)
    ok = (gaps > 4.0 * lipschitz) & (idx >= 1)
    m = jnp.min(jnp.where(ok, idx, d))
    m = jnp.where(m >= d, jnp.int32(0), m)           # no qualifying gap
    return jnp.clip(m.astype(jnp.float32) / d.astype(jnp.float32),
                    0.0, max_rate)


# ---------------------------------------------------------------------------
# Step 2 — Formula 15 aggregation
# ---------------------------------------------------------------------------

def aggregate_rates(
    rates: jnp.ndarray,       # [K+1] p*_k, index 0 = server
    sizes: jnp.ndarray,       # [K+1] n_k
    niid: jnp.ndarray,        # [K+1] D(P_k)
    eps: float = 1e-8,
) -> jnp.ndarray:
    w = jnp.asarray(sizes, jnp.float32) / (jnp.asarray(niid, jnp.float32) + eps)
    w = w / jnp.sum(w)
    return jnp.sum(w * jnp.asarray(rates, jnp.float32))


# ---------------------------------------------------------------------------
# Step 3 — global magnitude threshold -> per-layer rates
# ---------------------------------------------------------------------------

def global_threshold(params: Any, spec: PruneSpec, p_star: jnp.ndarray) -> jnp.ndarray:
    """V = |v_(floor(R * p*))| over all prunable weights (Alg. 3 lines 6-7)."""
    vals = jnp.concatenate(
        [jnp.abs(get_path(params, l.weight).astype(jnp.float32)).reshape(-1)
         for l in spec.layers]
    )
    r = vals.shape[0]
    k = jnp.clip((jnp.asarray(p_star, jnp.float32) * r).astype(jnp.int32), 0, r - 1)
    return jnp.sort(vals)[k]


def per_layer_rates(params: Any, spec: PruneSpec, threshold: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """p*_l = (#weights with |w| < V) / q_l per layer (Alg. 3 lines 9-11)."""
    out = {}
    for l in spec.layers:
        w = jnp.abs(get_path(params, l.weight).astype(jnp.float32))
        out[l.name] = jnp.mean((w < threshold).astype(jnp.float32))
    return out


# ---------------------------------------------------------------------------
# Step 4 — HRank filter selection
# ---------------------------------------------------------------------------

def feature_map_ranks(fmap: jnp.ndarray) -> jnp.ndarray:
    """HRank score per filter.

    fmap: [B, ..., d_l] activations with filters LAST.
      * conv maps  [B, H, W, d]: per-sample matrix rank of each [H, W] map,
        averaged over the batch (the HRank criterion).
      * 1-D features [B, d] (FFN neurons): rank degenerates; we use the
        batch singular-value mass |a| per neuron (activation energy), the
        shape-generalized analogue (see DESIGN.md Section 3).
    Returns [d_l] float scores — HIGHER = keep.
    """
    fmap = fmap.astype(jnp.float32)
    if fmap.ndim >= 4:
        return jnp.mean(feature_map_scores(fmap), axis=0)
    # [B, d] (or flatten middle dims): activation energy per neuron.
    flat = fmap.reshape(fmap.shape[0], -1, fmap.shape[-1])
    return jnp.mean(jnp.abs(flat), axis=(0, 1))


def feature_map_scores(fmap: jnp.ndarray) -> jnp.ndarray:
    """PER-SAMPLE HRank scores — [B, d_l], each row depending only on that
    sample's activations, so a batch-sharded forward can sum them and
    correct padded rows out exactly (the mesh path of
    ``fedap._finish_decision``).  ``feature_map_ranks`` is the batch mean
    of these scores: conv ranks per sample are integer-valued (<=
    min(H, W*)), so float32 sums over any probe batch are exact.
    """
    fmap = fmap.astype(jnp.float32)
    if fmap.ndim >= 4:
        b = fmap.shape[0]
        d = fmap.shape[-1]
        maps = jnp.moveaxis(fmap, -1, 1).reshape(b, d, fmap.shape[1], -1)  # [B,d,H,W*]
        s = jnp.linalg.svd(maps, compute_uv=False)                          # [B,d,min]
        tol = jnp.max(s, axis=-1, keepdims=True) * max(maps.shape[-2:]) * 1e-6
        return jnp.sum(s > tol, axis=-1).astype(jnp.float32)                # [B,d]
    flat = fmap.reshape(fmap.shape[0], -1, fmap.shape[-1])
    return jnp.mean(jnp.abs(flat), axis=1)


def select_filters(
    scores: jnp.ndarray,
    rate: jnp.ndarray | float,
    *,
    align: int | None = None,
    min_keep: int = 1,
) -> np.ndarray:
    """Keep the d_l - floor(rate * d_l) filters with the HIGHEST rank
    (Alg. 3 lines 13-14).  ``align`` rounds the kept count UP to a multiple
    (TPU lane alignment), so the realized rate p_l <= p*_l.

    Returns a sorted numpy index array (static — drives re-materialization).
    """
    scores = np.asarray(scores)
    d = scores.shape[0]
    keep = d - int(np.floor(float(rate) * d))
    keep = max(keep, min_keep)
    if align is not None and d >= align:
        keep = min(d, int(np.ceil(keep / align) * align))
    order = np.argsort(scores)[::-1]  # descending: highest rank first
    return np.sort(order[:keep])


# ---------------------------------------------------------------------------
# Structural shrink + masked (jit-static) variants
# ---------------------------------------------------------------------------

def shrink_params(params: Any, spec: PruneSpec, kept: Mapping[str, np.ndarray]) -> Any:
    """Re-materialize a genuinely smaller model: slice each pruned layer's
    filter axis and every coupled tensor (Alg. 3 line 15)."""
    for l in spec.layers:
        if l.name not in kept:
            continue
        idx = jnp.asarray(kept[l.name])
        w = get_path(params, l.weight)
        params = set_path(params, l.weight, jnp.take(w, idx, axis=l.filter_axis))
        for c in l.coupled:
            t = get_path(params, c.path)
            params = set_path(params, c.path, jnp.take(t, idx, axis=c.axis))
    return params


def filter_masks(params: Any, spec: PruneSpec, kept: Mapping[str, np.ndarray]) -> dict[str, jnp.ndarray]:
    """Binary keep-mask per layer ([d_l] of 0/1) for the static-shape masked
    execution mode (used inside long-lived jitted training programs where we
    cannot change shapes; the Pallas ``pruned_matmul`` kernel consumes the
    compacted index form instead)."""
    masks = {}
    for l in spec.layers:
        d = get_path(params, l.weight).shape[l.filter_axis]
        m = np.zeros((d,), np.float32)
        # `kept` is a host-resident index mapping (never traced), so this
        # numpy work constant-folds at trace time.
        idx = np.asarray(kept.get(l.name, np.arange(d)))  # lint: host-sync-ok
        m[idx] = 1.0
        masks[l.name] = jnp.asarray(m)
    return masks


def prunable_axes(params: Any, spec: PruneSpec) -> dict[Path, set]:
    """``{leaf path: axes}`` — the axes along which ``spec`` can zero each
    leaf: a layer's filter axis on its weight, and every coupled tensor's
    coupled axis.  A leaf absent from the map is never pruned."""
    axes: dict[Path, set] = {}
    for l in spec.layers:
        for path, axis in ((l.weight, l.filter_axis),
                           *((c.path, c.axis) for c in l.coupled)):
            path = tuple(path)
            ndim = get_path(params, path).ndim
            axes.setdefault(path, set()).add(axis % ndim)
    return axes


def param_masks(params: Any, spec: PruneSpec, kept: Mapping[str, np.ndarray]) -> Any:
    """Param-structured multiplicative keep-masks, in FACTORED form — the
    static-shape dual of :func:`shrink_params`.

    Returns a pytree with the structure of ``params`` (f32, 0/1) whose
    leaves keep only the axes along which ``spec`` can zero them
    (:func:`prunable_axes`); every other axis has size 1, so ``p * m``
    broadcasts.  A conv kernel [kh, kw, c_in, c_out] whose filters and
    inputs are both prunable gets a [1, 1, c_in, c_out] mask, its bias a
    [c_out] one, a never-pruned leaf ``(1,) * ndim``.  The shapes depend
    on ``spec`` alone, never on ``kept``, so all-ones masks (``kept={}``)
    already have the shapes of any decision's.  Zeros sit on exactly the
    coordinates ``shrink_params`` would slice away: the weight's filter
    axis AND every coupled tensor's coupled axis.

    Because the zeroed set is closed under the layer coupling (the pruned
    filter's weights, its bias, and the next layer's matching input slices
    all vanish), a masked model's forward activations and its gradients on
    the KEPT coordinates are exactly those of the re-materialized model for
    normalization-free architectures — and the gradients on masked
    coordinates are exactly zero, so masked training is self-sustaining
    inside a compiled scan.  (GroupNorm/LayerNorm models normalize over the
    zeroed channels and therefore only approximate the shrunk model.)
    """
    axes = prunable_axes(params, spec)

    def ones(kp, p):
        ax = axes.get(tuple(_norm_key(e) for e in kp), ())
        return jnp.ones(tuple(d if i in ax else 1
                              for i, d in enumerate(p.shape)), jnp.float32)

    masks = jax.tree_util.tree_map_with_path(ones, params)

    def mask_axis(m: jnp.ndarray, axis: int, idx: np.ndarray) -> jnp.ndarray:
        d = m.shape[axis]
        keep = np.zeros((d,), np.float32)
        keep[idx] = 1.0
        shape = [1] * m.ndim
        shape[axis] = d
        return m * jnp.asarray(keep).reshape(shape)

    for l in spec.layers:
        if l.name not in kept:
            continue
        idx = np.asarray(kept[l.name])
        masks = set_path(masks, l.weight,
                         mask_axis(get_path(masks, l.weight), l.filter_axis, idx))
        for c in l.coupled:
            masks = set_path(masks, c.path,
                             mask_axis(get_path(masks, c.path), c.axis, idx))
    return masks


def factor_masks(masks: Any, like: Any) -> Any:
    """Keep-masks reduced to the factored shapes of ``like`` (a tree of
    the same structure: the mask slot, or its shapes).

    A leaf that already has its slot's shape is returned as it is.  A
    larger one — a full parameter-shaped mask from a checkpoint or a prune
    artifact written before masks were factored — is cut to index 0 along
    every axis its slot holds at size 1, after checking that it is constant
    along those axes; otherwise it is a decision the factored slot cannot
    hold, and :class:`~repro.core.plan.CheckpointError` names the leaf."""
    from repro.core.plan import CheckpointError

    def one(kp, m, ref):
        target = tuple(ref.shape)
        if tuple(m.shape) == target:
            return m
        name = "/".join(str(_norm_key(e)) for e in kp)
        if len(m.shape) != len(target) or any(
                t not in (1, d) for d, t in zip(m.shape, target)):
            raise CheckpointError(
                f"keep-mask {name!r} has shape {tuple(m.shape)}, which does "
                f"not reduce to its factored shape {target}")
        host = np.asarray(m)
        head = host[tuple(slice(0, 1) if t == 1 else slice(None)
                          for t in target)]
        if not np.array_equal(np.broadcast_to(head, host.shape), host):
            raise CheckpointError(
                f"keep-mask {name!r} of shape {host.shape} varies along an "
                f"axis its factored shape {target} holds at size 1: FedAP "
                f"never zeroes along that axis, so this mask is refused")
        return head

    return jax.tree_util.tree_map_with_path(one, masks, like)


def model_flops_fraction(params_before: Any, params_after: Any) -> float:
    """Crude FLOP-reduction proxy: ratio of parameter counts (matmul FLOPs
    scale linearly in each pruned dimension)."""
    a = sum(int(x.size) for x in jax.tree.leaves(params_after))
    b = sum(int(x.size) for x in jax.tree.leaves(params_before))
    return a / b


# ---------------------------------------------------------------------------
# End-to-end FedAP driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FedAPConfig:
    prune_round: int = 30          # paper: pruning happens once, at round 30
    eps: float = 1e-8              # Formula 15
    align: int | None = None       # 128 on TPU; None on CPU repro
    max_rate: float = 0.9
    min_rate: float = 0.0          # compression-budget floor on p* (0 = off;
                                   # the eigen-gap rule alone decides, which
                                   # on easy tasks can be "prune nothing")
    probe_size: int = 32
    participants: int = 8          # devices (beyond the server) probed for p*_k

    def __post_init__(self):
        # Mirror FLConfig.__post_init__: bad switches fail HERE, at
        # construction, with a clear message — not as an opaque numpy
        # error deep inside fedap_decision's probe draw.
        if not 0.0 <= self.min_rate <= self.max_rate:
            raise ValueError(f"need 0 <= min_rate <= max_rate, got "
                             f"min_rate={self.min_rate} max_rate={self.max_rate}")
        if self.participants < 0:
            raise ValueError(
                f"participants must be >= 0, got {self.participants}")
        if self.probe_size < 1:
            raise ValueError(f"probe_size must be >= 1, got {self.probe_size}")
        if self.prune_round < 1:
            raise ValueError(
                f"prune_round must be >= 1, got {self.prune_round}")


def fedap_rates(
    *,
    spectra: Sequence[jnp.ndarray],
    lipschitzes: Sequence[jnp.ndarray],
    sizes: jnp.ndarray,
    niid: jnp.ndarray,
    params: Any,
    spec: PruneSpec,
    cfg: FedAPConfig,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Steps 1-3: per-participant rates -> Formula 15 -> per-layer rates."""
    rates = jnp.stack([
        expected_rate_from_spectrum(e, l, cfg.max_rate)
        for e, l in zip(spectra, lipschitzes)
    ])
    p_star = aggregate_rates(rates, sizes, niid, cfg.eps)
    thr = global_threshold(params, spec, p_star)
    return p_star, per_layer_rates(params, spec, thr)


def fedap_prune(
    params: Any,
    spec: PruneSpec,
    layer_rates: Mapping[str, jnp.ndarray],
    feature_maps: Mapping[str, jnp.ndarray],
    cfg: FedAPConfig,
) -> tuple[Any, dict[str, np.ndarray]]:
    """Step 4 + shrink.  Returns (pruned params, kept-index map)."""
    kept = {}
    for l in spec.layers:
        fkey = l.feature_key or l.name
        if fkey not in feature_maps:
            continue
        scores = feature_map_ranks(feature_maps[fkey])
        kept[l.name] = select_filters(scores, layer_rates[l.name], align=cfg.align)
    return shrink_params(params, spec, kept), kept
