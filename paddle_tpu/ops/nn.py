"""Neural-network ops: softmax/losses, conv, pooling, norms, embedding,
dropout.

Reference kernels: ``paddle/fluid/operators/softmax_op.cc`` (+cuDNN variant),
``softmax_with_cross_entropy_op.cc``, ``conv_op.cc``/``conv_cudnn_op.cu.cc``,
``pool_op.cc``, ``batch_norm_op.cc``, ``layer_norm_op.cc``,
``lookup_table_op.cc``, ``dropout_op.cc``.  TPU-native notes:

* conv lowers to ``lax.conv_general_dilated`` — XLA tiles it onto the MXU;
  there is no cuDNN-style algorithm-choice surface.
* batch/layer norm are plain jnp expressions; XLA fuses the reductions. The
  cross-replica variant (sync BN) is the same expression with ``lax.pmean``
  under a mesh axis — see ops/collective.py.
* ``softmax_with_cross_entropy`` is written as logsumexp−logit so its
  autodiff-derived grad is exactly (softmax − onehot), matching the
  reference's hand-written fused grad kernel.
"""

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

from .registry import register_op
from .common import normalize_axis


@register_op("softmax", inputs=["X"], outputs=["Out"])
def softmax(ctx, attrs, X):
    axis = int(attrs.get("axis", -1))
    # f32 internals under bf16 AMP (exp/sum accumulate in f32; XLA fuses
    # the casts) — the standard TPU attention-softmax recipe
    if X.dtype == jnp.bfloat16:
        return jax.nn.softmax(X.astype(jnp.float32), axis=axis).astype(
            X.dtype)
    return jax.nn.softmax(X, axis=axis)


@register_op("log_softmax", inputs=["X"], outputs=["Out"])
def log_softmax(ctx, attrs, X):
    axis = int(attrs.get("axis", -1))
    return jax.nn.log_softmax(X, axis=axis)


@register_op("cross_entropy", inputs=["X", "Label"], outputs=["Y"])
def cross_entropy(ctx, attrs, X, Label):
    soft_label = attrs.get("soft_label", False)
    ignore_index = int(attrs.get("ignore_index", -100))
    eps = 1e-12
    if soft_label:
        loss = -jnp.sum(Label * jnp.log(X + eps), axis=-1, keepdims=True)
    else:
        lab = Label.reshape(Label.shape[:-1]) if Label.shape[-1] == 1 else Label
        lab = lab.astype(jnp.int32)
        picked = jnp.take_along_axis(
            X, jnp.maximum(lab, 0)[..., None], axis=-1
        )[..., 0]
        loss = -jnp.log(picked + eps)
        loss = jnp.where(lab == ignore_index, jnp.zeros_like(loss), loss)
        loss = loss[..., None]
    return loss


@register_op(
    "softmax_with_cross_entropy",
    inputs=["Logits", "Label"],
    outputs=["Softmax", "Loss"],
    stateful_outputs=("Softmax",),
)
def softmax_with_cross_entropy(ctx, attrs, Logits, Label):
    axis = normalize_axis(int(attrs.get("axis", -1)), jnp.ndim(Logits))
    soft_label = attrs.get("soft_label", False)
    ignore_index = int(attrs.get("ignore_index", -100))
    # f32 internals for bf16 logits (AMP): the logsumexp reduction and the
    # log-prob gather fuse with the upcast, so no f32 logits tensor is
    # materialized in HBM
    in_dtype = Logits.dtype
    if in_dtype == jnp.bfloat16:
        Logits = Logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(Logits, axis=axis, keepdims=True)
    log_softmax = Logits - lse
    if soft_label:
        loss = -jnp.sum(Label * log_softmax, axis=axis, keepdims=True)
    else:
        lab = Label
        if lab.shape[axis] == 1:
            lab = jnp.squeeze(lab, axis=axis)
        lab = lab.astype(jnp.int32)
        picked = jnp.take_along_axis(
            log_softmax, jnp.expand_dims(jnp.maximum(lab, 0), axis), axis=axis
        )
        loss = -picked
        mask = jnp.expand_dims(lab, axis) == ignore_index
        loss = jnp.where(mask, jnp.zeros_like(loss), loss)
    return {"Softmax": jax.lax.stop_gradient(
        jnp.exp(log_softmax).astype(in_dtype)), "Loss": loss}


@register_op("dropout", inputs=["X"], outputs=["Out", "Mask"],
             stateful_outputs=("Mask",))
def dropout(ctx, attrs, X):
    p = float(attrs.get("dropout_prob", 0.5))
    is_test = attrs.get("is_test", False) or ctx.mode == "infer"
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl == "upscale_in_train":
            out = X
        else:
            out = X * jnp.asarray(1.0 - p, X.dtype)
        return {"Out": out, "Mask": jnp.ones_like(X, dtype=jnp.uint8)}
    seed = int(attrs.get("seed", 0))
    # a user seed pins the stream deterministically but must still vary
    # per step/op — fold it into the per-step key rather than replacing it
    key = ctx.rng()
    if seed:
        key = jax.random.fold_in(key, seed)
    keep = jax.random.bernoulli(key, 1.0 - p, jnp.shape(X))
    if impl == "upscale_in_train":
        scale = 1.0 / (1.0 - p) if p < 1.0 else 0.0
        out = jnp.where(keep, X * jnp.asarray(scale, X.dtype), jnp.zeros_like(X))
    else:
        out = jnp.where(keep, X, jnp.zeros_like(X))
    return {"Out": out, "Mask": keep.astype(jnp.uint8)}


def _lookup(W, Ids, padding_idx):
    ids = Ids
    squeeze_last = ids.ndim > 1 and ids.shape[-1] == 1
    if squeeze_last:
        ids = ids[..., 0]
    ids = ids.astype(jnp.int32)
    out = jnp.take(W, jnp.maximum(ids, 0), axis=0)
    if padding_idx is not None and padding_idx != -1:
        out = jnp.where(
            (ids == padding_idx)[..., None], jnp.zeros_like(out), out
        )
    return out


@register_op("lookup_table", inputs=["W", "Ids"], outputs=["Out"])
def lookup_table(ctx, attrs, W, Ids):
    # reference op: Ids shaped [..., 1] int64 (lookup_table_op.cc); grad wrt W
    # is the vjp of take = scatter-add, XLA's native sparse-grad form on TPU
    return _lookup(W, Ids, attrs.get("padding_idx", -1))


@register_op("lookup_table_v2", inputs=["W", "Ids"], outputs=["Out"])
def lookup_table_v2(ctx, attrs, W, Ids):
    return _lookup(W, Ids, attrs.get("padding_idx", -1))


@register_op("embedding", inputs=["W", "Ids"], outputs=["Out"])
def embedding(ctx, attrs, W, Ids):
    return _lookup(W, Ids, attrs.get("padding_idx", -1))


@register_op("lookup_sparse_table", inputs=["W", "Ids"], outputs=["Out"])
def lookup_sparse_table(ctx, attrs, W, Ids):
    """PS-era auto-grown sparse table lookup
    (``lookup_sparse_table_op.cc``: rows materialize in the pserver hash
    table on first touch, init'd U(min,max)).  TPU-native the table is a
    dense row-sharded array, so every row already exists — the lookup
    degenerates to the plain gather; auto_grown_table/is_test only
    control the reference's hash-table bookkeeping and have no dense
    equivalent."""
    return _lookup(W, Ids, attrs.get("padding_idx", -1))


@register_op("one_hot", inputs=["X"], outputs=["Out"], no_grad=True)
def one_hot(ctx, attrs, X):
    depth = int(attrs.get("depth"))
    ids = X
    if ids.ndim > 1 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    return jax.nn.one_hot(ids.astype(jnp.int32), depth, dtype=jnp.float32)


@register_op("one_hot_v2", inputs=["X"], outputs=["Out"], no_grad=True)
def one_hot_v2(ctx, attrs, X):
    depth = int(attrs.get("depth"))
    return jax.nn.one_hot(X.astype(jnp.int32), depth, dtype=jnp.float32)


@register_op(
    "layer_norm",
    inputs=["X", "Scale", "Bias"],
    outputs=["Y", "Mean", "Variance"],
    stateful_outputs=("Mean", "Variance"),
)
def layer_norm(ctx, attrs, X, Scale, Bias):
    begin = int(attrs.get("begin_norm_axis", 1))
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, jnp.ndim(X)))
    x32 = X.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    # deliberately the TWO-pass variance (not batch_norm's single-pass
    # E[x^2]-E[x]^2): per-row LN stats see drifting residual-stream
    # means where the cancellation form loses all precision, and norm
    # is 0.2% of the profiled step — there is no perf win to buy here
    var = jnp.mean(jnp.square(x32 - mean), axis=axes, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    # Scale/Bias are stored flattened over the normalized dims
    # (layer_norm_op.cc InferShape); broadcast them back over X's tail
    bshape = (1,) * begin + jnp.shape(X)[begin:]
    if Scale is not None:
        y = y * Scale.astype(jnp.float32).reshape(bshape)
    if Bias is not None:
        y = y + Bias.astype(jnp.float32).reshape(bshape)
    return {
        "Y": y.astype(X.dtype),
        "Mean": jnp.squeeze(mean, axes).reshape(-1),
        "Variance": jnp.squeeze(var, axes).reshape(-1),
    }


@register_op(
    "batch_norm",
    inputs=["X", "Scale", "Bias", "Mean", "Variance"],
    outputs=["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
    stateful_outputs=("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"),
)
def batch_norm(ctx, attrs, X, Scale, Bias, Mean, Variance):
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or attrs.get("use_global_stats", False)
    layout = attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else jnp.ndim(X) - 1
    reduce_axes = tuple(i for i in range(jnp.ndim(X)) if i != c_axis)
    bshape = tuple(
        jnp.shape(X)[i] if i == c_axis else 1 for i in range(jnp.ndim(X))
    )
    x32 = X.astype(jnp.float32)
    if is_test:
        use_mean, use_var = Mean, Variance
        mean_out, var_out = Mean, Variance
        saved_mean, saved_var = Mean, Variance
    else:
        bm = jnp.mean(x32, axis=reduce_axes)
        # single-pass variance E[x^2] - E[x]^2: both reductions read x
        # ONCE (XLA fuses them into one sweep) instead of the dependent
        # two-pass mean(square(x - mean)) form, which forces a second
        # full pass over the activation per BN site.  f32 accumulation;
        # clamped >= 0 against cancellation on near-constant channels.
        bv = jnp.maximum(
            jnp.mean(jnp.square(x32), axis=reduce_axes) - jnp.square(bm),
            0.0)
        use_mean, use_var = bm, bv
        mean_out = Mean * momentum + bm * (1 - momentum)
        var_out = Variance * momentum + bv * (1 - momentum)
        saved_mean, saved_var = bm, jax.lax.rsqrt(bv + eps)
    y = (x32 - use_mean.reshape(bshape)) * jax.lax.rsqrt(
        use_var.reshape(bshape) + eps
    )
    y = y * Scale.reshape(bshape) + Bias.reshape(bshape)
    return {
        "Y": y.astype(X.dtype),
        "MeanOut": jax.lax.stop_gradient(mean_out),
        "VarianceOut": jax.lax.stop_gradient(var_out),
        "SavedMean": jax.lax.stop_gradient(saved_mean),
        "SavedVariance": jax.lax.stop_gradient(saved_var),
    }


def _conv_padding(paddings, ksize, dilations):
    if isinstance(paddings, str):
        return paddings  # 'SAME' / 'VALID'
    if len(paddings) == len(ksize):
        return [(p, p) for p in paddings]
    # already pairs
    return [
        (paddings[2 * i], paddings[2 * i + 1]) for i in range(len(ksize))
    ]


def _conv_transpose_padding(paddings, ksize, dilations):
    """Map the reference's symmetric transpose-conv padding p (output =
    (in-1)*s + dilated_k - 2p) onto jax.lax.conv_transpose's input-side
    pads of the fractionally-strided conv: lo = hi = d*(k-1) - p."""
    if isinstance(paddings, str):
        return paddings
    if len(paddings) == len(ksize):
        pairs = [(int(p), int(p)) for p in paddings]
    else:
        pairs = [(int(paddings[2 * i]), int(paddings[2 * i + 1]))
                 for i in range(len(ksize))]
    return [
        (d * (int(k) - 1) - lo, d * (int(k) - 1) - hi)
        for (lo, hi), k, d in zip(pairs, ksize, dilations)
    ]


def _conv_nd(ctx, attrs, Input, Filter, nd):
    strides = [int(s) for s in attrs.get("strides", [1] * nd)]
    paddings = attrs.get("paddings", [0] * nd)
    dilations = [int(d) for d in attrs.get("dilations", [1] * nd)]
    groups = int(attrs.get("groups", 1) or 1)
    layout = attrs.get("data_format", "NCHW")
    ksize = jnp.shape(Filter)[2:]
    pad = _conv_padding(paddings, ksize, dilations)
    if nd == 2:
        dn_in = "NCHW" if layout in ("NCHW", "AnyLayout") else "NHWC"
        dn = (dn_in, "OIHW", dn_in)
    else:
        dn_in = "NCDHW" if layout in ("NCDHW", "AnyLayout", "NCHW") else "NDHWC"
        dn = (dn_in, "OIDHW", dn_in)
    # NO preferred_element_type here: jax's conv transpose rule feeds the
    # fp32 cotangent of the widened output straight into a conv against
    # the bf16 filter and dies with a dtype mismatch — which would crash
    # every AMP conv BACKWARD at trace time (found pre-staging the
    # resnet50 AMP bench).  The natural bf16×bf16→bf16 conv is
    # numerically identical on TPU anyway: the MXU always accumulates in
    # fp32 internally and rounds once on output.
    out = jax.lax.conv_general_dilated(
        Input,
        Filter,
        window_strides=strides,
        padding=pad,
        rhs_dilation=dilations,
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    return out.astype(jnp.result_type(Input, Filter))


@register_op("conv2d", inputs=["Input", "Filter"], outputs=["Output"])
def conv2d(ctx, attrs, Input, Filter):
    return _conv_nd(ctx, attrs, Input, Filter, 2)


@register_op("depthwise_conv2d", inputs=["Input", "Filter"], outputs=["Output"])
def depthwise_conv2d(ctx, attrs, Input, Filter):
    return _conv_nd(ctx, attrs, Input, Filter, 2)


@register_op("conv3d", inputs=["Input", "Filter"], outputs=["Output"])
def conv3d(ctx, attrs, Input, Filter):
    return _conv_nd(ctx, attrs, Input, Filter, 3)


@register_op("conv2d_transpose", inputs=["Input", "Filter"], outputs=["Output"])
def conv2d_transpose(ctx, attrs, Input, Filter):
    strides = [int(s) for s in attrs.get("strides", [1, 1])]
    paddings = attrs.get("paddings", [0, 0])
    dilations = [int(d) for d in attrs.get("dilations", [1, 1])]
    groups = int(attrs.get("groups", 1) or 1)
    ksize = jnp.shape(Filter)[2:]
    pad = _conv_transpose_padding(paddings, ksize, dilations)

    # kernel stays in the reference's [C_in, C_out/g, kh, kw] layout: under
    # transpose_kernel=True that is spec OIHW (O = the fwd conv's output =
    # C_in) — verified against the scatter oracle incl. C_in != C_out and
    # paddings (round-1 used IOHW, which breaks for C_in != C_out)
    def one(inp, flt):
        return jax.lax.conv_transpose(
            inp,
            flt,
            strides=strides,
            padding=pad,
            rhs_dilation=dilations,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            transpose_kernel=True,
        )

    if groups == 1:
        return one(Input, Filter)
    # grouped (conv_transpose_op.cc:67: out channels = filter_dims[1]*g):
    # static per-group slices; XLA fuses the g small convs + concat.
    return jnp.concatenate(
        [one(x, f) for x, f in zip(jnp.split(Input, groups, axis=1),
                                   jnp.split(Filter, groups, axis=0))],
        axis=1)


def _pool_nd(attrs, X, nd):
    """Shared max/avg pooling (pool_op.cc 2-D/3-D): global/adaptive
    handling + the trace-time-constant init for reduce_window (its grad
    rule, select-and-scatter, cannot linearize a traced init value)."""
    import numpy as np

    ptype = attrs.get("pooling_type", "max")
    ksize = [int(k) for k in attrs.get("ksize", [2] * nd)]
    strides = [int(s) for s in attrs.get("strides", [2] * nd)]
    paddings = [int(p) for p in attrs.get("paddings", [0] * nd)]
    global_pooling = attrs.get("global_pooling", False)
    adaptive = attrs.get("adaptive", False)
    exclusive = attrs.get("exclusive", True)
    # same predicate as the conv lowering (anything not NC* is
    # channels-last) — a mismatch would silently build a mixed-layout
    # model that traces fine and computes garbage
    channels_last = attrs.get("data_format", "NCHW") not in (
        "NCHW", "NCDHW", "AnyLayout")
    spatial = (jnp.shape(X)[1:-1] if channels_last
               else jnp.shape(X)[2:])
    if global_pooling or (adaptive and ksize == [1] * nd):
        ksize = list(spatial)
        strides = [1] * nd
        paddings = [0] * nd
    elif adaptive:
        ksize = [s // k for s, k in zip(spatial, ksize)]
        strides = list(ksize)
        paddings = [0] * nd
    if channels_last:
        window = (1,) + tuple(ksize) + (1,)
        wstrides = (1,) + tuple(strides) + (1,)
        pad = ((0, 0),) + tuple((p, p) for p in paddings) + ((0, 0),)
    else:
        window = (1, 1) + tuple(ksize)
        wstrides = (1, 1) + tuple(strides)
        pad = ((0, 0), (0, 0)) + tuple((p, p) for p in paddings)
    if ptype == "max":
        if jnp.issubdtype(X.dtype, jnp.floating):
            import ml_dtypes

            np_dt = (ml_dtypes.bfloat16 if X.dtype == jnp.bfloat16
                     else np.dtype(X.dtype))
            init = np.asarray(-np.inf, np_dt)
        else:
            init = np.asarray(np.iinfo(np.dtype(X.dtype)).min, X.dtype)
        return jax.lax.reduce_window(
            X, init, jax.lax.max, window, wstrides, pad)
    s = jax.lax.reduce_window(
        X.astype(jnp.float32), 0.0, jax.lax.add, window, wstrides, pad)
    if exclusive and any(paddings):
        ones_shape = ((1,) + tuple(spatial) + (1,) if channels_last
                      else (1, 1) + tuple(spatial))
        ones = jnp.ones(ones_shape, jnp.float32)
        cnt = jax.lax.reduce_window(
            ones, 0.0, jax.lax.add, window, wstrides, pad)
        out = s / cnt
    else:
        import math as _math

        out = s / float(_math.prod(ksize))
    return out.astype(X.dtype)


@register_op("pool2d", inputs=["X"], outputs=["Out"])
def pool2d(ctx, attrs, X):
    return _pool_nd(attrs, X, 2)


@register_op("accuracy", inputs=["Out", "Indices", "Label"],
             outputs=["Accuracy", "Correct", "Total"], no_grad=True)
def accuracy(ctx, attrs, Out, Indices, Label):
    lab = Label
    if lab.ndim > 1 and lab.shape[-1] == 1:
        lab = lab[..., 0]
    hit = jnp.any(Indices == lab[:, None].astype(Indices.dtype), axis=1)
    correct = jnp.sum(hit.astype(jnp.int32))
    total = jnp.asarray(lab.shape[0], jnp.int32)
    return {
        "Accuracy": (correct / total).astype(jnp.float32).reshape(1),
        "Correct": correct.reshape(1),
        "Total": total.reshape(1),
    }


@register_op("huber_loss", inputs=["X", "Y"], outputs=["Out", "Residual"],
             stateful_outputs=("Residual",))
def huber_loss(ctx, attrs, X, Y):
    delta = attrs.get("delta", 1.0)
    r = Y - X
    ar = jnp.abs(r)
    loss = jnp.where(
        ar <= delta, 0.5 * jnp.square(r), delta * (ar - 0.5 * delta)
    )
    return {"Out": loss, "Residual": jax.lax.stop_gradient(r)}


@register_op("square_error_cost", inputs=["X", "Y"], outputs=["Out"])
def square_error_cost(ctx, attrs, X, Y):
    return jnp.square(X - Y)


@register_op("sigmoid_cross_entropy_with_logits", inputs=["X", "Label"],
             outputs=["Out"])
def sigmoid_cross_entropy_with_logits(ctx, attrs, X, Label):
    ignore_index = attrs.get("ignore_index", -100)
    loss = jnp.maximum(X, 0) - X * Label + jnp.log1p(jnp.exp(-jnp.abs(X)))
    loss = jnp.where(Label == ignore_index, jnp.zeros_like(loss), loss)
    if attrs.get("normalize", False):
        norm = jnp.maximum(
            jnp.sum((Label != ignore_index).astype(loss.dtype)), 1.0
        )
        loss = loss / norm
    return loss


@register_op("smooth_l1_loss", inputs=["X", "Y", "InsideWeight", "OutsideWeight"],
             outputs=["Diff", "Out"], stateful_outputs=("Diff",))
def smooth_l1_loss(ctx, attrs, X, Y, InsideWeight, OutsideWeight):
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = X - Y
    if InsideWeight is not None:
        d = d * InsideWeight
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * jnp.square(d), ad - 0.5 / s2)
    if OutsideWeight is not None:
        loss = loss * OutsideWeight
    loss = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    return {"Diff": jax.lax.stop_gradient(d), "Out": loss}


@register_op("label_smooth", inputs=["X", "PriorDist"], outputs=["Out"])
def label_smooth(ctx, attrs, X, PriorDist):
    eps = attrs.get("epsilon", 0.0)
    if PriorDist is not None:
        return (1 - eps) * X + eps * PriorDist
    return (1 - eps) * X + eps / X.shape[-1]


@register_op("prelu", inputs=["X", "Alpha"], outputs=["Out"])
def prelu(ctx, attrs, X, Alpha):
    mode = attrs.get("mode", "all")
    if mode == "all":
        a = Alpha.reshape(())
    elif mode == "channel":
        a = Alpha.reshape((1, -1) + (1,) * (jnp.ndim(X) - 2))
    else:
        a = Alpha.reshape((1,) + jnp.shape(X)[1:])
    return jnp.where(X >= 0, X, a * X)


def _flash_site(ctx, attrs, Q, K, V, BiasQK=None):
    from .pallas.flash_attention import routes_to_kernel

    return routes_to_kernel(Q, K, BiasQK, V)


@register_op("fused_multihead_attention", inputs=["Q", "K", "V", "BiasQK"],
             outputs=["Out"], kernel_residuals=_flash_site)
def fused_multihead_attention(ctx, attrs, Q, K, V, BiasQK=None):
    """Fused scaled-dot-product attention (reference analogue: the
    fusion_* attention kernels under ``paddle/fluid/operators/fused/``).
    Q: [B, H, T, Dh]; K: [B, Hkv, Tk, Dh]; V: [B, Hkv, Tk, Dv], where Dv
    may differ from Dh (latent attention: 192 against 128) and is the
    output's width, and Hkv may divide H (grouped-query heads: query
    head h reads head ``h // (H // Hkv)`` of K and V, which are never
    expanded); BiasQK: additive key bias [B, Tk] or [B,1,1,Tk]; attr
    ``window`` (with ``causal``): a query sees the last ``window`` keys
    up to its own.  Lowered to the
    Pallas FlashAttention-2 TPU kernel when
    profitable, XLA attention otherwise (ops/pallas/flash_attention.py);
    its backward is the custom-vjp flash backward, reached through the
    registry's generic grad derivation: over the residuals (``m``, ``l``)
    of the forward op's own kernel call where the Executor lowers both
    ops in one call and the site routes to the kernel (``_flash_site``),
    else over a ``jax.vjp`` that re-derives the forward.  In a recompute
    region (``ctx.region``) a kernel site keeps its forward kernel's
    ``(o, m, l)`` across the region and the grad op's re-run, which
    computes Q, K and V again, takes those and runs no forward kernel."""
    from .pallas.flash_attention import flash_attention

    causal = bool(attrs.get("causal", False))
    scale = attrs.get("scale", None)
    if scale is not None:
        scale = float(scale)
    rate = float(attrs.get("dropout_rate", 0.0) or 0.0)
    if attrs.get("is_test"):
        rate = 0.0  # clone(for_test=True) flips this attr (framework.py)
    seed = None
    if rate > 0.0 and ctx.mode == "train":
        # per-step, per-op seed from the deterministic ctx key chain (the
        # grad op's recompute draws the SAME seed → identical mask)
        seed = jax.random.randint(ctx.rng(), (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)
    else:
        rate = 0.0
    attend = functools.partial(
        flash_attention, Q, K, V, bias=BiasQK, causal=causal, sm_scale=scale,
        dropout_rate=rate, dropout_seed=seed,
        window=attrs.get("window") or None)
    return _keeping_across_region(
        ctx, "fused_multihead_attention", attend,
        lambda: _flash_site(ctx, attrs, Q, K, V, BiasQK))


def _keeping_across_region(ctx, op_type, call, routes):
    """``call()``, a kernel site's lowering, in or out of a recompute
    region (``ctx.region``): the region's forward keeps what the forward
    kernel gave (``call(return_residuals=True)``; the expert layer: its
    plan and its first block's gate and up products), the grad op's
    re-run hands it back (``call(residuals=...)``) and runs no forward
    kernel; where nothing crossed (no kernel here, or the region's forward
    was lowered in another call) the kernel runs again, noted as
    ``recomputed`` where ``routes()``."""
    region = ctx.region
    if region is None:
        return call()
    if not region.rerun:
        out, region.values[ctx.op_id] = call(return_residuals=True)
        return out
    kept = region.values.get(ctx.op_id)
    if kept is None:
        if routes():
            ctx.note_residual_site(op_type, "recomputed")
        return call()
    ctx.note_residual_site(op_type, "kept_across_region",
                           sum(x.size * x.dtype.itemsize for x in kept))
    return call(residuals=kept)


def _fused_ln_rate(ctx, attrs):
    rate = float(attrs.get("dropout_prob", 0.0) or 0.0)
    if attrs.get("is_test") or ctx.mode == "infer":
        rate = 0.0
    return rate


def _fused_ln_site(ctx, attrs, X, Residual, Scale, Bias):
    from .pallas.fused_ln import routes_to_kernel

    shape = jnp.shape(X)
    return routes_to_kernel(
        jax.ShapeDtypeStruct((math.prod(shape[:-1]), shape[-1]), X.dtype),
        _fused_ln_rate(ctx, attrs))


@register_op("fused_dropout_add_ln", inputs=["X", "Residual", "Scale",
                                             "Bias"],
             outputs=["Out"], kernel_residuals=_fused_ln_site)
def fused_dropout_add_ln(ctx, attrs, X, Residual, Scale, Bias):
    """``layer_norm(residual + dropout(x))`` in one Pallas pass
    (ops/pallas/fused_ln.py; reference analogue: the fused_elemwise /
    layer_norm JIT kernels).  X/Residual: [..., D] normalized over the
    last axis; Scale/Bias: [D]."""
    from .pallas.fused_ln import fused_dropout_add_ln as _fused

    rate = _fused_ln_rate(ctx, attrs)
    eps = float(attrs.get("epsilon", 1e-5))
    seed = None
    if rate > 0.0:
        # per-step, per-op seed from the deterministic ctx key chain
        # (the grad op's recompute draws the SAME seed/mask)
        seed = jax.random.randint(ctx.rng(), (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)
    shape = jnp.shape(X)
    d = shape[-1]
    out = _fused(X.reshape(-1, d), Residual.reshape(-1, d), Scale, Bias,
                 dropout_rate=rate, eps=eps, seed=seed)
    return out.reshape(shape)


@register_op("rms_norm", inputs=["X", "Scale"], outputs=["Y"])
def rms_norm(ctx, attrs, X, Scale):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, the
    statistics in float32 whatever X's dtype."""
    eps = float(attrs.get("epsilon", 1e-6))
    x32 = X.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + eps)
    if Scale is not None:
        y = y * Scale.astype(jnp.float32)
    return y.astype(X.dtype)


@register_op("rotary_embedding", inputs=["X"], outputs=["Out"])
def rotary_embedding(ctx, attrs, X):
    """Rotary position embedding on part of a head.  X: [..., T, Dh],
    positions 0..T-1 along the last axis but one; the ``rotary_dim``
    features from ``offset`` on are rotated, the rest pass through.
    ``interleaved``: pair i is features (2i, 2i+1) of the part, else
    (i, i + rotary_dim/2); its angle is ``t * theta^(-2i/rotary_dim)``,
    times ``frequency_scale[i]`` where that attr is given (a factor a
    pair: position interpolation, NTK and YaRN blends), and cos and sin
    are both times ``magnitude``.  Computed in float32."""
    t, dh = jnp.shape(X)[-2], jnp.shape(X)[-1]
    offset = int(attrs.get("offset", 0))
    rot = int(attrs.get("rotary_dim", 0)) or dh - offset
    theta = float(attrs.get("theta", 10000.0))
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    if attrs.get("frequency_scale"):
        inv = inv * jnp.asarray(attrs["frequency_scale"], jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)                  # [T, rot/2]
    magnitude = float(attrs.get("magnitude", 1.0))
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    part = X[..., offset:offset + rot].astype(jnp.float32)
    if attrs.get("interleaved", True):
        pairs = part.reshape(part.shape[:-1] + (rot // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                        axis=-1).reshape(part.shape)
    else:
        a, b = part[..., :rot // 2], part[..., rot // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                              axis=-1)
    return jnp.concatenate(
        [X[..., :offset], out.astype(X.dtype), X[..., offset + rot:]],
        axis=-1)


@register_op("swiglu", inputs=["X", "Y"], outputs=["Out"])
def swiglu(ctx, attrs, X, Y):
    """``silu(x) * y``, the product in float32."""
    return (jax.nn.silu(X.astype(jnp.float32))
            * Y.astype(jnp.float32)).astype(X.dtype)


@register_op("relu2", inputs=["X"], outputs=["Out"])
def relu2(ctx, attrs, X):
    """``relu(x)^2``, in float32 (the activation of a relu² MLP)."""
    return jnp.square(jax.nn.relu(X.astype(jnp.float32))).astype(X.dtype)


@register_op("causal_conv1d", inputs=["X", "Filter", "Bias"],
             outputs=["Out"])
def causal_conv1d(ctx, attrs, X, Filter, Bias=None):
    """Depthwise causal convolution along time: X [B, T, C], Filter [C,
    K], Bias [C]: ``out[t, c] = sum_k Filter[c, k] X[t - K + 1 + k, c] +
    Bias[c]``, X zero before the first position (the first T outputs of a
    left-padded convolution).  Computed in float32."""
    t, k = jnp.shape(X)[1], jnp.shape(Filter)[-1]
    x = jnp.pad(X.astype(jnp.float32), [(0, 0), (k - 1, 0), (0, 0)])
    w = Filter.astype(jnp.float32)
    out = sum(x[:, i:i + t, :] * w[:, i] for i in range(k))
    if Bias is not None:
        out = out + Bias.astype(jnp.float32)
    return out.astype(X.dtype)


@register_op("rms_norm_gated", inputs=["X", "Z", "Scale"], outputs=["Y"])
def rms_norm_gated(ctx, attrs, X, Z, Scale):
    """``g = x * silu(z)``, then ``g / sqrt(mean(g^2) + eps) * scale``
    in float32 (Mamba-2's gated norm): the mean over the whole last axis
    (one group), or with attr ``group_size`` over each run of that many
    channels of it (``G`` groups: Nemotron-H's ``MambaRMSNormGated``)."""
    eps = float(attrs.get("epsilon", 1e-6))
    g = X.astype(jnp.float32) * jax.nn.silu(Z.astype(jnp.float32))
    size = int(attrs.get("group_size", 0) or 0)
    if size:
        shape = g.shape
        g = g.reshape(shape[:-1] + (shape[-1] // size, size))
    y = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    if size:
        y = y.reshape(shape)
    if Scale is not None:
        y = y * Scale.astype(jnp.float32)
    return y.astype(X.dtype)


def _ssd_site(ctx, attrs, X, Dt, ALog, B, C, D=None, DtBias=None):
    from .pallas.ssd_scan import routes_to_kernel

    return routes_to_kernel(X, B, int(attrs.get("chunk_size", 256)))


@register_op("ssd_scan", inputs=["X", "Dt", "ALog", "B", "C", "D", "DtBias"],
             outputs=["Y"], kernel_residuals=_ssd_site)
def ssd_scan(ctx, attrs, X, Dt, ALog, B, C, D=None, DtBias=None):
    """Mamba-2's state-space-dual scan (``ops/pallas/ssd_scan.py``): X [B,
    T, H, P], Dt [B, T, H], B and C [B, T, N] (one group, shared by every
    head) or [B, T, G, N] (head h reads group ``h // (H / G)``), ALog, D,
    DtBias [H].  ``Δ = softplus(Dt + DtBias)``, ``A =
    -exp(ALog)``, both in float32; per head ``S_t = exp(Δ_t A) S_{t-1} +
    Δ_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``, in chunks of
    ``chunk_size``.  Lowered to the
    Pallas kernels on the TPU, to the same chunked algorithm in XLA ops
    elsewhere; outside a region the grad op reuses the forward kernel's
    residuals (``_ssd_site``).  In a recompute region (``ctx.region``) a
    kernel site keeps what its forward kernel gave (y and the state each
    chunk began with) across the region, and the re-run runs no forward
    kernel."""
    from .pallas import ssd_scan as kernels

    dt = Dt.astype(jnp.float32)
    if DtBias is not None:
        dt = dt + DtBias.astype(jnp.float32)
    dt = jax.nn.softplus(dt)
    scan = functools.partial(
        kernels.ssd_scan, X, dt, -jnp.exp(ALog.astype(jnp.float32)), B, C,
        D, chunk=int(attrs.get("chunk_size", 256)))
    # a forward kernel traced in a region's re-run counts as such
    with kernels.rerunning() if ctx.region is not None and ctx.region.rerun \
            else contextlib.nullcontext():
        return _keeping_across_region(
            ctx, "ssd_scan", scan,
            lambda: _ssd_site(ctx, attrs, X, Dt, ALog, B, C))


def _rows_of(shape):
    """Rows of a [..., D] var flattened to [N, D]; -1 where a dim is."""
    lead = [int(d) for d in shape[:-1]]
    return -1 if any(d < 0 for d in lead) else math.prod(lead)


def _moe_route_shapes(op, block):
    # from the metadata: the unknown batch's sentinel times tokens times
    # top_k passes 2**31, and eval_shape of the sort would refuse it
    x = block._find_var_recursive(op.input("X")[0])
    for slot, dtype in (("Index", "int32"), ("Gate", "float32")):
        v = block._find_var_recursive(op.output(slot)[0])
        v.shape = (_rows_of(x.shape), int(op.attrs["top_k"]))
        v.dtype = dtype
    used = block._find_var_recursive(op.output("BiasOut")[0])
    used.shape = tuple(block._find_var_recursive(op.input("Bias")[0]).shape)
    used.dtype = "float32"
    if op.output("XOut"):
        kept = block._find_var_recursive(op.output("XOut")[0])
        kept.shape, kept.dtype = (_rows_of(x.shape), x.shape[-1]), x.dtype


def _moe_experts_shapes(op, block):
    x = block._find_var_recursive(op.input("X")[0])
    out = block._find_var_recursive(op.output("Out")[0])
    out.shape, out.dtype = tuple(x.shape), x.dtype
    rows = block._find_var_recursive(op.output("Rows")[0])
    rows.shape, rows.dtype = (len(op.input("WDown")),), "int32"


@register_op("moe_route", inputs=["X", "Weight", "Bias"],
             outputs=["Index", "Gate", "BiasOut", "XOut"],
             infer_shape=_moe_route_shapes)
def moe_route(ctx, attrs, X, Weight, Bias):
    """The router of a top-k expert layer over ALL its experts
    (``parallel/moe.py`` ``sigmoid_topk_route``; attr ``score_func``
    ``sigmoid`` or ``softmax``).  X: [..., D]; Weight:
    [D, E]; Bias: [E], added to the scores (under ``softmax`` to their
    logarithms) for the choice only.  Index:
    [N, top_k] int32 and Gate: [N, top_k] float32, N the rows of X;
    BiasOut: [E], the bias the choice was made with: Bias, or with
    ``center_bias`` outside test mode minus each expert's mean score
    over these rows (``softmax``: minus the log-score that its share,
    ``top_k / E``, of the rows lie above).  XOut (``keep_input``): the rows of X as the router
    read them, behind an optimization barrier: the compiler keeps more
    than bfloat16 between the ops it fuses, so a copy of X made by
    another op need not hold the very numbers this one was given."""
    from ..parallel.moe import sigmoid_topk_route

    center = bool(attrs.get("center_bias")) and not (
        attrs.get("is_test") or ctx.mode == "infer")
    x = X.reshape(-1, jnp.shape(X)[-1])
    if attrs.get("keep_input"):
        x = jax.lax.optimization_barrier(x)
    idx, gates, used = sigmoid_topk_route(
        x, Weight, Bias, int(attrs["top_k"]),
        float(attrs.get("scale", 1.0)),
        bool(attrs.get("norm_topk_prob", True)), center,
        attrs.get("score_func", "sigmoid"))
    return {"Index": idx, "Gate": gates, "BiasOut": used, "XOut": x}


@register_op("moe_experts", inputs=["X", "Index", "Gate", "WGate*", "WUp*",
                                    "WDown*"],
             outputs=["Out", "Rows"], stateful_outputs=("Rows",),
             infer_shape=_moe_experts_shapes)
def moe_experts(ctx, attrs, X, Index, Gate, WGate, WUp, WDown):
    """The part of a top-k expert layer that the experts held here give
    (``parallel/moe.py`` ``held_experts_ffn``): WGate, WUp (each expert's
    [D, F]) and WDown ([F, D]) are experts ``first_expert .. first_expert
    + held - 1`` of those Index counts over, stacked here for the grouped
    products.  Attr ``activation``: ``swiglu`` (the default, ``E(x) =
    W_down (silu(W_gate x) * W_up x)``) or ``relu2`` (``E(x) = W_down
    relu(W_up x)^2``, no WGate).  Dropless.  Out: X's shape; Rows: [held]
    int32, the rows each held expert was given this step.  In a recompute
    region (``ctx.region``) the site keeps its plan (the choices' order and
    the rows) and the loop's first block's in-products (gate and up, or
    up) across the region; the re-run walks that plan and its backward
    takes the products in place of that block's forward in-products."""
    from ..parallel.moe import held_experts_ffn

    shape = jnp.shape(X)
    ffn = functools.partial(
        held_experts_ffn, X.reshape(-1, shape[-1]), Index, Gate,
        jnp.stack(WGate) if WGate else None, jnp.stack(WUp),
        jnp.stack(WDown), first=int(attrs.get("first_expert", 0)),
        scope=ctx.part_scope, total=int(attrs.get("experts_total", 0)),
        activation=attrs.get("activation", "swiglu"))
    out, rows = _keeping_across_region(ctx, "moe_experts", ffn,
                                       lambda: True)
    return {"Out": out.reshape(shape), "Rows": rows}


@register_op("moe_count_rows", inputs=["Rows", "Index", "Stats"],
             outputs=["StatsOut"], no_grad=True)
def moe_count_rows(ctx, attrs, Rows, Index, Stats):
    """Adds one step to an expert layer's counters, on the device:
    Stats is int32 [held + 3], the rows given to each held expert so
    far, then the rows possible (tokens * top_k), the rows dispatch
    moved (``parallel/moe.py``: ``block_rows`` times the blocks the
    layer's loop ran; attr ``experts_total`` as ``moe_experts``' own) and
    the steps.  int32 wraps;
    ``observability.runtime.publish_moe_counters`` reads differences."""
    from ..parallel.moe import block_rows, blocks_run

    block = block_rows(Index.size, Rows.shape[0],
                       int(attrs.get("experts_total", 0)))
    step = jnp.concatenate([
        Rows.astype(jnp.int32),
        jnp.stack([jnp.int32(Index.size), block * blocks_run(Rows, block),
                   jnp.int32(1)])])
    return Stats + step


@register_op("fused_bias_act", inputs=["X", "Bias"], outputs=["Out"])
def fused_bias_act(ctx, attrs, X, Bias):
    """``act(x + bias)`` in one op — the fusion pipeline's rewrite of
    Fluid's ``fuse_elewise_add_act_pass`` (the fc bias+activation tail).
    Bit-exact by construction: it calls the SAME registered
    ``elementwise_add`` broadcast helper and the SAME registered
    activation lowering the unfused pair uses."""
    from .common import fluid_broadcast
    from .registry import get_op_def

    x, b = fluid_broadcast(X, Bias, attrs.get("axis", -1))
    y = jnp.add(x, b)
    act = attrs.get("act_type", "relu")
    return get_op_def(act).fn(ctx, dict(attrs), y)


@register_op(
    "fused_conv_bn_act",
    inputs=["Input", "Filter", "Scale", "Bias", "Mean", "Variance"],
    outputs=["Out", "MeanOut", "VarianceOut"],
    stateful_outputs=("MeanOut", "VarianceOut"),
)
def fused_conv_bn_act(ctx, attrs, Input, Filter, Scale, Bias, Mean,
                      Variance):
    """conv2d → batch_norm → activation as one op (the reference's
    ``fuse_bn_act_ops`` pass + inference conv+bn fold, fused at train
    time too).  The conv runs through the SAME ``_conv_nd`` lowering as
    the unfused op (XLA owns the MXU schedule); the BN statistics use
    the SAME single-pass form as ``batch_norm``; the normalize+affine+
    act epilogue is one Pallas VMEM pass when eligible
    (ops/pallas/conv_bn_act.py) and the bit-exact XLA composite
    otherwise.  Running-stat updates (MeanOut/VarianceOut) ride along
    exactly as in ``batch_norm``."""
    from .pallas.conv_bn_act import bn_act_epilogue, epilogue_eligible

    conv = _conv_nd(ctx, attrs, Input, Filter, 2)
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) \
        or attrs.get("use_global_stats", False)
    layout = attrs.get("data_layout", attrs.get("data_format", "NCHW"))
    if layout == "AnyLayout":
        layout = "NCHW"
    c_axis = 1 if layout == "NCHW" else jnp.ndim(conv) - 1
    reduce_axes = tuple(i for i in range(jnp.ndim(conv)) if i != c_axis)
    bshape = tuple(
        jnp.shape(conv)[i] if i == c_axis else 1
        for i in range(jnp.ndim(conv)))
    x32 = conv.astype(jnp.float32)
    if is_test:
        use_mean, use_var = Mean, Variance
        mean_out, var_out = Mean, Variance
    else:
        bm = jnp.mean(x32, axis=reduce_axes)
        # single-pass E[x^2] - E[x]^2, clamped — identical to batch_norm
        bv = jnp.maximum(
            jnp.mean(jnp.square(x32), axis=reduce_axes) - jnp.square(bm),
            0.0)
        use_mean, use_var = bm, bv
        mean_out = Mean * momentum + bm * (1 - momentum)
        var_out = Variance * momentum + bv * (1 - momentum)
    act = attrs.get("act_type", "") or "identity"
    rows = 1
    for i in reduce_axes:
        rows *= jnp.shape(conv)[i]
    channels = jnp.shape(conv)[c_axis]
    if c_axis == jnp.ndim(conv) - 1 \
            and epilogue_eligible(rows, channels, act):
        rstd = jax.lax.rsqrt(use_var.astype(jnp.float32) + eps)
        out2d = bn_act_epilogue(
            conv.reshape(-1, channels), Scale, Bias, use_mean, rstd,
            act=act)
        y = out2d.reshape(jnp.shape(conv))
    else:
        # the XLA composite — the exact float sequence of the unfused
        # batch_norm lowering followed by the registered activation, so
        # fusion-on matches fusion-off bit-for-bit on this path
        y = (x32 - use_mean.reshape(bshape)) * jax.lax.rsqrt(
            use_var.reshape(bshape) + eps)
        y = y * Scale.reshape(bshape) + Bias.reshape(bshape)
        y = y.astype(conv.dtype)
        if act != "identity":
            from .registry import get_op_def

            y = get_op_def(act).fn(ctx, dict(attrs), y)
    return {
        "Out": y,
        "MeanOut": jax.lax.stop_gradient(mean_out),
        "VarianceOut": jax.lax.stop_gradient(var_out),
    }


@register_op("selu", inputs=["X"], outputs=["Out"])
def selu(ctx, attrs, X):
    """scale * (max(0,x) + min(0, alpha*(exp(x)-1))) (selu_op.cc)."""
    scale = float(attrs.get("scale", 1.0507009873554805))
    alpha = float(attrs.get("alpha", 1.6732632423543772))
    return scale * jnp.where(X > 0, X, alpha * (jnp.exp(X) - 1.0))


@register_op("multiplex", inputs=["X*", "Ids"], outputs=["Out"])
def multiplex(ctx, attrs, X, Ids):
    """Row-wise select among k candidate tensors (multiplex_op.cc):
    out[i] = X[ids[i]][i]."""
    stacked = jnp.stack(X, axis=0)  # [k, B, ...]
    ids = jnp.reshape(Ids, (-1,)).astype(jnp.int32)
    rows = jnp.arange(stacked.shape[1])
    return stacked[ids, rows]


@register_op("sampling_id", inputs=["X"], outputs=["Out"], no_grad=True)
def sampling_id(ctx, attrs, X):
    """Sample one column index per row of a probability matrix
    (sampling_id_op.cc)."""
    key = ctx.rng()
    return jax.random.categorical(
        key, jnp.log(jnp.maximum(X, 1e-38)), axis=-1
    ).astype(jnp.int64)


@register_op("uniform_random_batch_size_like", inputs=["Input"],
             outputs=["Out"], no_grad=True)
def uniform_random_batch_size_like(ctx, attrs, Input):
    from .common import resolve_dtype

    shape = [int(s) for s in attrs["shape"]]
    idx_in = int(attrs.get("input_dim_idx", 0))
    idx_out = int(attrs.get("output_dim_idx", 0))
    shape[idx_out] = Input.shape[idx_in]
    dtype = resolve_dtype(attrs.get("dtype", 5))
    lo = float(attrs.get("min", -1.0))
    hi = float(attrs.get("max", 1.0))
    return jax.random.uniform(ctx.rng(), shape, dtype, lo, hi)


@register_op("gaussian_random_batch_size_like", inputs=["Input"],
             outputs=["Out"], no_grad=True)
def gaussian_random_batch_size_like(ctx, attrs, Input):
    from .common import resolve_dtype

    shape = [int(s) for s in attrs["shape"]]
    idx_in = int(attrs.get("input_dim_idx", 0))
    idx_out = int(attrs.get("output_dim_idx", 0))
    shape[idx_out] = Input.shape[idx_in]
    dtype = resolve_dtype(attrs.get("dtype", 5))
    mean = float(attrs.get("mean", 0.0))
    std = float(attrs.get("std", 1.0))
    return mean + std * jax.random.normal(ctx.rng(), shape, dtype)


@register_op("add_position_encoding", inputs=["X"], outputs=["Out"])
def add_position_encoding(ctx, attrs, X):
    """alpha*x + beta*PE with PE[j, k<half] = sin(j / 10000^(k/(half-1))),
    PE[j, half+k] = cos(same) (add_position_encoding_op.h)."""
    alpha = float(attrs.get("alpha", 1.0))
    beta = float(attrs.get("beta", 1.0))
    b, t, d = X.shape
    half = d // 2
    j = jnp.arange(t, dtype=jnp.float32)[:, None]
    k = jnp.arange(half, dtype=jnp.float32)[None, :]
    denom = jnp.power(10000.0, k / max(half - 1, 1))
    val = j / denom
    parts = [jnp.sin(val), jnp.cos(val)]
    if d % 2:
        # odd feature dim: the reference kernel leaves the last column
        # unwritten; define it as passthrough (pe = 0) instead of UB
        parts.append(jnp.zeros((t, 1), jnp.float32))
    pe = jnp.concatenate(parts, axis=1)  # [T, D]
    return alpha * X + beta * pe[None, :, :].astype(X.dtype)


@register_op("hash", inputs=["X"], outputs=["Out"], no_grad=True)
def hash_op(ctx, attrs, X):
    """num_hash integer hashes of each id row, mod mod_by (hash_op.h).
    The reference uses XXH64; here a splitmix64-style mix — deterministic
    and well-distributed, but NOT bit-identical to xxhash (documented
    deviation: hashed-embedding training is seed-compatible within this
    framework, not across frameworks)."""
    num_hash = int(attrs.get("num_hash", 1))
    mod_by = int(attrs.get("mod_by", 1))
    x = X.astype(jnp.uint32)
    # combine each row's ids into one 32-bit state per hash seed
    outs = []
    for seed in range(num_hash):
        h = jnp.full(x.shape[:-1], 0x9E3779B9 * (seed + 1), jnp.uint32)
        for i in range(x.shape[-1]):
            v = x[..., i]
            v = v * jnp.uint32(0x85EBCA6B)
            v = v ^ (v >> 13)
            v = v * jnp.uint32(0xC2B2AE35)
            h = (h ^ v) * jnp.uint32(0x01000193)
        outs.append((h % jnp.uint32(mod_by)).astype(jnp.int64))
    out = jnp.stack(outs, axis=-1)  # [..., num_hash]
    return out[..., None] if X.ndim == 2 else out


@register_op("data_norm", inputs=["X", "BatchSize", "BatchSum",
                                  "BatchSquareSum"],
             outputs=["Y", "Means", "Scales"],
             stateful_outputs=("Means", "Scales"))
def data_norm(ctx, attrs, X, BatchSize, BatchSum, BatchSquareSum):
    """CTR feature normalization (data_norm_op.cc): means = sum/size,
    scales = sqrt(size/square_sum); y = (x - means) * scales.  The stat
    accumulators are persistable params updated by the training loop."""
    means = BatchSum / BatchSize
    scales = jnp.sqrt(BatchSize / BatchSquareSum)
    y = (X - means[None, :]) * scales[None, :]
    return {"Y": y, "Means": means, "Scales": scales}


@register_op("spectral_norm", inputs=["Weight", "U", "V"], outputs=["Out"])
def spectral_norm(ctx, attrs, Weight, U, V):
    """Power-iteration spectral normalization (spectral_norm_op.h):
    repeat {v = W^T u / ||.||; u = W v / ||.||}; sigma = u^T W v;
    out = W / sigma.  dim selects the 'height' axis (transposed first)."""
    dim = int(attrs.get("dim", 0))
    power_iters = int(attrs.get("power_iters", 1))
    eps = float(attrs.get("eps", 1e-12))
    w = Weight
    perm = None
    if dim != 0:
        perm = [dim] + [i for i in range(w.ndim) if i != dim]
        w = jnp.transpose(w, perm)
    h = w.shape[0]
    mat = w.reshape(h, -1)
    u = jnp.reshape(U, (h,))
    v = jnp.reshape(V, (-1,))
    for _ in range(power_iters):
        v = mat.T @ u
        v = v / (jnp.linalg.norm(v) + eps)
        u = mat @ v
        u = u / (jnp.linalg.norm(u) + eps)
    u = jax.lax.stop_gradient(u)
    v = jax.lax.stop_gradient(v)
    sigma = u @ (mat @ v)
    out = w / sigma
    if perm is not None:
        inv = [perm.index(i) for i in range(len(perm))]
        out = jnp.transpose(out, inv)
    return out


@register_op("row_conv", inputs=["X", "Filter"], outputs=["Out"])
def row_conv(ctx, attrs, X, Filter):
    """Lookahead row convolution (row_conv_op.cc): for padded [B,T,D]
    input and [K,D] filter, out[t] = sum_{i<K, t+i<T} x[t+i] * w[i]."""
    k = Filter.shape[0]
    b, t, d = X.shape
    out = jnp.zeros_like(X)
    for i in range(k):
        shifted = jnp.pad(X[:, i:, :], ((0, 0), (0, i), (0, 0)))
        out = out + shifted * Filter[i][None, None, :]
    return out


def _sampler_logq(sampler, ids, n):
    """log q(id) under the negative sampler (nce_op.h samplers):
    0=uniform, 1=log-uniform (Zipf: q(c)=log((c+2)/(c+1))/log(n+1))."""
    if sampler == 1:
        ids_f = ids.astype(jnp.float32)
        q = jnp.log((ids_f + 2.0) / (ids_f + 1.0)) / jnp.log(n + 1.0)
        return jnp.log(jnp.maximum(q, 1e-20))
    return jnp.full(jnp.shape(ids), -jnp.log(float(n)))


def _draw_negatives(ctx, sampler, k, n, seed=0):
    key = ctx.rng()
    if seed:
        key = jax.random.fold_in(key, int(seed))
    if sampler == 1:
        # inverse-CDF of the Zipfian log-uniform distribution
        u = jax.random.uniform(key, (k,))
        ids = jnp.exp(u * jnp.log(n + 1.0)) - 1.0
        return jnp.clip(ids.astype(jnp.int32), 0, n - 1)
    return jax.random.randint(key, (k,), 0, n, jnp.int32)


@register_op("nce", inputs=["Input", "Label", "Weight", "Bias",
                            "SampleWeight"],
             outputs=["Cost", "SampleLogits", "SampleLabels"],
             stateful_outputs=("SampleLogits", "SampleLabels"))
def nce(ctx, attrs, Input, Label, Weight, Bias, SampleWeight):
    """Noise-contrastive estimation (nce_op.h): binary logistic loss for
    the true class against k sampled noise classes with the sampler-
    probability correction s - log(k*q)."""
    k = int(attrs.get("num_neg_samples", 10))
    n = int(attrs.get("num_total_classes"))
    sampler = int(attrs.get("sampler", 0))
    B = Input.shape[0]
    lbl = jnp.reshape(Label, (B, -1))[:, 0].astype(jnp.int32)
    neg = _draw_negatives(ctx, sampler, k, n,
                          attrs.get("seed", 0))  # [K], shared across batch
    # true-class logit: row-wise dot, not a [B,B] matmul
    s_true = jnp.einsum("bd,bd->b", Input, Weight[lbl])[:, None]
    if Bias is not None:
        s_true = s_true + jnp.reshape(Bias, (-1,))[lbl][:, None]
    s_neg = jnp.matmul(Input, Weight[neg].T)  # [B, K]
    if Bias is not None:
        s_neg = s_neg + jnp.reshape(Bias, (-1,))[neg][None, :]
    adj_true = s_true - (jnp.log(float(k)) + _sampler_logq(sampler, lbl, n)
                         )[:, None]
    adj_neg = s_neg - (jnp.log(float(k)) + _sampler_logq(sampler, neg, n)
                       )[None, :]
    # -log sigma(true) - sum log(1 - sigma(neg)), in stable softplus form
    cost = (jnp.logaddexp(0.0, -adj_true)[:, 0]
            + jnp.sum(jnp.logaddexp(0.0, adj_neg), axis=1))
    if SampleWeight is not None:
        cost = cost * jnp.reshape(SampleWeight, (-1,))
    sample_logits = jnp.concatenate([s_true, s_neg], axis=1)
    sample_labels = jnp.concatenate(
        [lbl[:, None], jnp.broadcast_to(neg[None, :], (B, k))], axis=1)
    return {"Cost": cost[:, None], "SampleLogits": sample_logits,
            "SampleLabels": sample_labels.astype(jnp.int64)}


@register_op("hierarchical_sigmoid", inputs=["X", "W", "Label", "Bias"],
             outputs=["Out", "PreOut"], stateful_outputs=("PreOut",))
def hierarchical_sigmoid(ctx, attrs, X, W, Label, Bias):
    """Hierarchical sigmoid over the complete binary 'SimpleCode' tree
    (hierarchical_sigmoid_op.h + framework MatrixBitCode): for class c,
    code = c + num_classes; node j has index (code>>(j+1))-1 and bit
    (code>>j)&1; loss = sum_j BCE(sigmoid(x.w_idx + b_idx), bit)."""
    n = int(attrs.get("num_classes"))
    B = X.shape[0]
    lbl = jnp.reshape(Label, (B,)).astype(jnp.int32)
    code = lbl + n
    import math as _math

    max_len = int(_math.ceil(_math.log2(2 * n)))
    losses = jnp.zeros((B,), jnp.float32)
    length = jnp.floor(
        jnp.log2(code.astype(jnp.float32) + 1e-6)).astype(jnp.int32)
    for j in range(max_len):
        idx = (code >> (j + 1)) - 1          # [B]
        bit = ((code >> j) & 1).astype(jnp.float32)
        valid = j < length
        idx_safe = jnp.clip(idx, 0, W.shape[0] - 1)
        pre = jnp.sum(X * W[idx_safe], axis=1)
        if Bias is not None:
            pre = pre + jnp.reshape(Bias, (-1,))[idx_safe]
        # BCE with logit `pre`, label `bit`
        term = jnp.logaddexp(0.0, pre) - bit * pre
        losses = losses + jnp.where(valid, term, 0.0)
    return {"Out": losses[:, None],
            "PreOut": jnp.zeros((B, max_len), jnp.float32)}


@register_op("sampled_softmax_with_cross_entropy",
             inputs=["Logits", "Label"], outputs=["Softmax", "Loss"],
             stateful_outputs=("Softmax",))
def sampled_softmax_with_cross_entropy(ctx, attrs, Logits, Label):
    """Softmax CE over {true, S sampled} classes with -log q correction
    (reference python sampled_softmax_with_cross_entropy →
    sample_logits_op + softmax; single fused lowering here)."""
    s_count = int(attrs.get("num_samples", 10))
    B, C = Logits.shape
    lbl = jnp.reshape(Label, (B,)).astype(jnp.int32)
    neg = _draw_negatives(ctx, 1, s_count, C, attrs.get("seed", 0))
    s_true = jnp.take_along_axis(Logits, lbl[:, None], axis=1)
    s_neg = jnp.take(Logits, neg, axis=1)
    adj_true = s_true - _sampler_logq(1, lbl, C)[:, None]
    adj_neg = s_neg - _sampler_logq(1, neg, C)[None, :]
    if attrs.get("remove_accidental_hits", True):
        # a sampled negative equal to the true label would double-count
        # the true class in the denominator; mask it out (reference
        # sample_logits_op remove_accidental_hits)
        hit = neg[None, :] == lbl[:, None]
        adj_neg = jnp.where(hit, -1e30, adj_neg)
    z = jnp.concatenate([adj_true, adj_neg], axis=1)  # true at col 0
    logp = jax.nn.log_softmax(z, axis=1)
    return {"Loss": -logp[:, :1], "Softmax": jnp.exp(logp)}


@register_op("conv3d_transpose", inputs=["Input", "Filter"],
             outputs=["Output"])
def conv3d_transpose(ctx, attrs, Input, Filter):
    """NCDHW transposed 3-D conv (conv3d_transpose variant of
    conv_transpose_op.cc)."""
    strides = [int(s) for s in attrs.get("strides", [1, 1, 1])]
    paddings = attrs.get("paddings", [0, 0, 0])
    dilations = [int(d) for d in attrs.get("dilations", [1, 1, 1])]
    groups = int(attrs.get("groups", 1) or 1)

    ksize = jnp.shape(Filter)[2:]
    pad = _conv_transpose_padding(paddings, ksize, dilations)

    def one(inp, flt):
        return jax.lax.conv_transpose(
            inp, flt, strides=strides, padding=pad,
            rhs_dilation=dilations,
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
            transpose_kernel=True,
        )

    if groups == 1:
        return one(Input, Filter)
    return jnp.concatenate(
        [one(x, f) for x, f in zip(jnp.split(Input, groups, axis=1),
                                   jnp.split(Filter, groups, axis=0))],
        axis=1)


@register_op("pool3d", inputs=["X"], outputs=["Out"])
def pool3d(ctx, attrs, X):
    """NCDHW pooling (pool_op.cc 3-D registration)."""
    return _pool_nd(attrs, X, 3)


@register_op("group_norm", inputs=["X", "Scale", "Bias"],
             outputs=["Y", "Mean", "Variance"],
             stateful_outputs=("Mean", "Variance"))
def group_norm_op(ctx, attrs, X, Scale, Bias):
    """Group normalization (group_norm_op.cc): NCHW, stats per (n, group)."""
    g = int(attrs.get("groups", 1))
    eps = float(attrs.get("epsilon", 1e-5))
    n, c = X.shape[0], X.shape[1]
    xg = X.reshape((n, g, c // g) + X.shape[2:]).astype(jnp.float32)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    # single-pass E[x^2]-E[x]^2 (see batch_norm); stats in f32 — the
    # cancellation form needs full-precision accumulation under AMP
    var = jnp.maximum(
        jnp.mean(jnp.square(xg), axis=axes, keepdims=True)
        - jnp.square(mean), 0.0)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(X.shape)
    shape = (1, c) + (1,) * (X.ndim - 2)
    if Scale is not None:
        y = y * Scale.reshape(shape).astype(jnp.float32)
    if Bias is not None:
        y = y + Bias.reshape(shape).astype(jnp.float32)
    return {"Y": y.astype(X.dtype), "Mean": mean.reshape(n, g),
            "Variance": var.reshape(n, g)}


@register_op(
    "sync_batch_norm",
    inputs=["X", "Scale", "Bias", "Mean", "Variance"],
    outputs=["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
    stateful_outputs=("MeanOut", "VarianceOut", "SavedMean",
                      "SavedVariance"),
)
def sync_batch_norm(ctx, attrs, X, Scale, Bias, Mean, Variance):
    """Cross-device batch norm (sync_batch_norm_op.cu).  Under jit+GSPMD
    batch stats of a batch-sharded input are ALREADY global, so this is
    the plain batch_norm lowering registered under the sync name
    (tests/test_grad_accum_syncbn.py proves the global-stats parity)."""
    from .registry import get_op_def

    return get_op_def("batch_norm").fn(ctx, attrs, X, Scale, Bias, Mean,
                                       Variance)
