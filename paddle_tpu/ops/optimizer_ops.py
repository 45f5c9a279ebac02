"""Optimizer update ops (reference: ``paddle/fluid/operators/optimizers/`` —
sgd_op.cc, momentum_op.cc, adam_op.cc, adagrad_op.cc, rmsprop_op.cc,
lamb_op.cc, lars_momentum_op.cc …).

Each op reads Param (+ accumulators) and writes the same variables (the
executor's SSA env rebinds the names), so under jit the whole optimizer
update fuses into the step function and the param buffers are donated —
the TPU analogue of the reference's in-place updates plus its fused-optimizer
graph passes (``ir/fuse_optimizer_ops_pass/``), which XLA fusion subsumes.
"""

import jax.numpy as jnp

from .registry import register_op


def _lr(LearningRate, dtype):
    return LearningRate.reshape(()).astype(dtype)


@register_op("sgd", inputs=["Param", "Grad", "LearningRate"],
             outputs=["ParamOut"], no_grad=True)
def sgd(ctx, attrs, Param, Grad, LearningRate):
    return Param - _lr(LearningRate, Param.dtype) * Grad.astype(Param.dtype)


@register_op(
    "momentum",
    inputs=["Param", "Grad", "Velocity", "LearningRate"],
    outputs=["ParamOut", "VelocityOut"],
    no_grad=True,
)
def momentum(ctx, attrs, Param, Grad, Velocity, LearningRate):
    mu = attrs.get("mu", 0.9)
    lr = _lr(LearningRate, Param.dtype)
    g = Grad.astype(Param.dtype)
    v = jnp.asarray(mu, Param.dtype) * Velocity + g
    if attrs.get("use_nesterov", False):
        p = Param - (g + jnp.asarray(mu, Param.dtype) * v) * lr
    else:
        p = Param - lr * v
    return {"ParamOut": p, "VelocityOut": v}


@register_op(
    "adam",
    inputs=["Param", "Grad", "LearningRate", "Moment1", "Moment2",
            "Beta1Pow", "Beta2Pow"],
    outputs=["ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut"],
    no_grad=True,
)
def adam(ctx, attrs, Param, Grad, LearningRate, Moment1, Moment2,
         Beta1Pow, Beta2Pow):
    beta1 = attrs.get("beta1", 0.9)
    beta2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr = _lr(LearningRate, jnp.float32)
    g = Grad.astype(jnp.float32)
    m1 = Moment1.astype(jnp.float32)
    m2 = Moment2.astype(jnp.float32)
    b1p = Beta1Pow.reshape(()).astype(jnp.float32)
    b2p = Beta2Pow.reshape(()).astype(jnp.float32)
    m1n = beta1 * m1 + (1 - beta1) * g
    m2n = beta2 * m2 + (1 - beta2) * jnp.square(g)
    # Beta{1,2}Pow hold beta^t when this op reads them (init=beta, advanced
    # after use) — matches reference adam_op.h:93 bias correction
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p = Param.astype(jnp.float32) - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    return {
        "ParamOut": p.astype(Param.dtype),
        "Moment1Out": m1n.astype(Moment1.dtype),
        "Moment2Out": m2n.astype(Moment2.dtype),
        "Beta1PowOut": (b1p * beta1).reshape(Beta1Pow.shape).astype(Beta1Pow.dtype),
        "Beta2PowOut": (b2p * beta2).reshape(Beta2Pow.shape).astype(Beta2Pow.dtype),
    }


@register_op(
    "adamax",
    inputs=["Param", "Grad", "LearningRate", "Moment", "InfNorm", "Beta1Pow"],
    outputs=["ParamOut", "MomentOut", "InfNormOut"],
    no_grad=True,
)
def adamax(ctx, attrs, Param, Grad, LearningRate, Moment, InfNorm, Beta1Pow):
    beta1 = attrs.get("beta1", 0.9)
    beta2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr = _lr(LearningRate, Param.dtype)
    m = beta1 * Moment + (1 - beta1) * Grad
    inf = jnp.maximum(beta2 * InfNorm, jnp.abs(Grad) + eps)
    b1p = Beta1Pow.reshape(()).astype(Param.dtype)
    p = Param - (lr / (1 - b1p)) * (m / inf)
    return {"ParamOut": p, "MomentOut": m, "InfNormOut": inf}


@register_op(
    "adagrad",
    inputs=["Param", "Grad", "Moment", "LearningRate"],
    outputs=["ParamOut", "MomentOut"],
    no_grad=True,
)
def adagrad(ctx, attrs, Param, Grad, Moment, LearningRate):
    eps = attrs.get("epsilon", 1e-6)
    m = Moment + jnp.square(Grad)
    p = Param - _lr(LearningRate, Param.dtype) * Grad / (jnp.sqrt(m) + eps)
    return {"ParamOut": p, "MomentOut": m}


@register_op(
    "decayed_adagrad",
    inputs=["Param", "Grad", "Moment", "LearningRate"],
    outputs=["ParamOut", "MomentOut"],
    no_grad=True,
)
def decayed_adagrad(ctx, attrs, Param, Grad, Moment, LearningRate):
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    m = decay * Moment + (1 - decay) * jnp.square(Grad)
    p = Param - _lr(LearningRate, Param.dtype) * Grad / (jnp.sqrt(m) + eps)
    return {"ParamOut": p, "MomentOut": m}


@register_op(
    "adadelta",
    inputs=["Param", "Grad", "AvgSquaredGrad", "AvgSquaredUpdate"],
    outputs=["ParamOut", "AvgSquaredGradOut", "AvgSquaredUpdateOut"],
    no_grad=True,
)
def adadelta(ctx, attrs, Param, Grad, AvgSquaredGrad, AvgSquaredUpdate):
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    asg = rho * AvgSquaredGrad + (1 - rho) * jnp.square(Grad)
    update = -jnp.sqrt((AvgSquaredUpdate + eps) / (asg + eps)) * Grad
    asu = rho * AvgSquaredUpdate + (1 - rho) * jnp.square(update)
    return {
        "ParamOut": Param + update,
        "AvgSquaredGradOut": asg,
        "AvgSquaredUpdateOut": asu,
    }


@register_op(
    "rmsprop",
    inputs=["Param", "Grad", "MeanSquare", "MeanGrad", "Moment",
            "LearningRate"],
    outputs=["ParamOut", "MomentOut", "MeanSquareOut", "MeanGradOut"],
    no_grad=True,
)
def rmsprop(ctx, attrs, Param, Grad, MeanSquare, MeanGrad, Moment,
            LearningRate):
    eps = attrs.get("epsilon", 1e-10)
    decay = attrs.get("decay", 0.9)
    mom_coef = attrs.get("momentum", 0.0)
    centered = attrs.get("centered", False)
    lr = _lr(LearningRate, Param.dtype)
    ms = decay * MeanSquare + (1 - decay) * jnp.square(Grad)
    if centered:
        mg = decay * MeanGrad + (1 - decay) * Grad
        denom = ms - jnp.square(mg) + eps
    else:
        mg = MeanGrad
        denom = ms + eps
    mom = mom_coef * Moment + lr * Grad / jnp.sqrt(denom)
    return {
        "ParamOut": Param - mom,
        "MomentOut": mom,
        "MeanSquareOut": ms,
        "MeanGradOut": mg,
    }


@register_op(
    "ftrl",
    inputs=["Param", "SquaredAccumulator", "LinearAccumulator", "Grad",
            "LearningRate"],
    outputs=["ParamOut", "SquaredAccumOut", "LinearAccumOut"],
    no_grad=True,
)
def ftrl(ctx, attrs, Param, SquaredAccumulator, LinearAccumulator, Grad,
         LearningRate):
    l1 = attrs.get("l1", 0.0) + 1e-10
    l2 = attrs.get("l2", 0.0) + 1e-10
    lr_power = attrs.get("lr_power", -0.5)
    lr = _lr(LearningRate, Param.dtype)
    new_sq = SquaredAccumulator + jnp.square(Grad)
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(SquaredAccumulator)) / lr
    else:
        sigma = (new_sq ** (-lr_power) - SquaredAccumulator ** (-lr_power)) / lr
    linear = LinearAccumulator + Grad - sigma * Param
    if lr_power == -0.5:
        x = l2 + jnp.sqrt(new_sq) / lr
    else:
        x = l2 + new_sq ** (-lr_power) / lr
    pre_shrink = (l1 * jnp.sign(linear) - linear) / x
    p = jnp.where(jnp.abs(linear) > l1, pre_shrink, jnp.zeros_like(Param))
    return {"ParamOut": p, "SquaredAccumOut": new_sq, "LinearAccumOut": linear}


@register_op(
    "lars_momentum",
    inputs=["Param", "Grad", "Velocity", "LearningRate"],
    outputs=["ParamOut", "VelocityOut"],
    no_grad=True,
)
def lars_momentum(ctx, attrs, Param, Grad, Velocity, LearningRate):
    mu = attrs.get("mu", 0.9)
    coeff = attrs.get("lars_coeff", 0.001)
    decay = attrs.get("lars_weight_decay", 0.0005)
    lr = _lr(LearningRate, jnp.float32)
    p32, g32 = Param.astype(jnp.float32), Grad.astype(jnp.float32)
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g32)))
    local_lr = jnp.where(
        (p_norm > 0) & (g_norm > 0),
        lr * coeff * p_norm / (g_norm + decay * p_norm + 1e-12),
        lr,
    )
    v = mu * Velocity.astype(jnp.float32) + local_lr * (g32 + decay * p32)
    return {
        "ParamOut": (p32 - v).astype(Param.dtype),
        "VelocityOut": v.astype(Velocity.dtype),
    }


@register_op(
    "lamb",
    inputs=["Param", "Grad", "LearningRate", "Moment1", "Moment2",
            "Beta1Pow", "Beta2Pow"],
    outputs=["ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut"],
    no_grad=True,
)
def lamb(ctx, attrs, Param, Grad, LearningRate, Moment1, Moment2,
         Beta1Pow, Beta2Pow):
    beta1 = attrs.get("beta1", 0.9)
    beta2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    lr = _lr(LearningRate, jnp.float32)
    p32 = Param.astype(jnp.float32)
    g32 = Grad.astype(jnp.float32)
    b1p = Beta1Pow.reshape(()).astype(jnp.float32)
    b2p = Beta2Pow.reshape(()).astype(jnp.float32)
    m1 = beta1 * Moment1.astype(jnp.float32) + (1 - beta1) * g32
    m2 = beta2 * Moment2.astype(jnp.float32) + (1 - beta2) * jnp.square(g32)
    m1_hat = m1 / (1 - b1p)
    m2_hat = m2 / (1 - b2p)
    update = m1_hat / (jnp.sqrt(m2_hat) + eps) + wd * p32
    w_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
    u_norm = jnp.sqrt(jnp.sum(jnp.square(update)))
    ratio = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
    p = p32 - lr * ratio * update
    return {
        "ParamOut": p.astype(Param.dtype),
        "Moment1Out": m1.astype(Moment1.dtype),
        "Moment2Out": m2.astype(Moment2.dtype),
        "Beta1PowOut": (b1p * beta1).reshape(Beta1Pow.shape).astype(Beta1Pow.dtype),
        "Beta2PowOut": (b2p * beta2).reshape(Beta2Pow.shape).astype(Beta2Pow.dtype),
    }


@register_op(
    "average_accumulates",
    inputs=["param", "in_sum_1", "in_sum_2", "in_sum_3",
            "in_num_accumulates", "in_old_num_accumulates",
            "in_num_updates"],
    outputs=["out_sum_1", "out_sum_2", "out_sum_3", "out_num_accumulates",
             "out_old_num_accumulates", "out_num_updates"],
    no_grad=True,
)
def average_accumulates(ctx, attrs, param, in_sum_1, in_sum_2, in_sum_3,
                        in_num_accumulates, in_old_num_accumulates,
                        in_num_updates):
    """Sliding-window parameter-sum accumulator for ModelAverage
    (reference ``paddle/fluid/operators/average_accumulates_op.h:30``):
    three-tier sums avoid fp precision loss; the window restarts when
    num_accumulates exceeds min(max_average_window,
    num_updates*average_window).  The C++ kernel's host-side branches
    become jnp.where selects so the whole update stays inside jit."""
    s1, s2, s3 = in_sum_1, in_sum_2, in_sum_3
    na, ona, nu = in_num_accumulates, in_old_num_accumulates, in_num_updates
    k_max = 16384  # kMaxNumAccumulates, precision-preserving fold period
    avg_window = float(attrs.get("average_window", 0.0))
    max_w = int(attrs.get("max_average_window", 10000))
    min_w = int(attrs.get("min_average_window", 10000))

    nu = nu + 1
    na = na + 1
    s1 = s1 + param.astype(s1.dtype)
    fold = (nu % k_max) == 0
    s2 = jnp.where(fold, s2 + s1, s2)
    s1 = jnp.where(fold, jnp.zeros_like(s1), s1)
    window = jnp.minimum(
        jnp.asarray(max_w, jnp.float32), nu.astype(jnp.float32) * avg_window
    )
    restart = (na >= min_w) & (na.astype(jnp.float32) >= window)
    s3 = jnp.where(restart, s1 + s2, s3)
    s1 = jnp.where(restart, jnp.zeros_like(s1), s1)
    s2 = jnp.where(restart, jnp.zeros_like(s2), s2)
    ona = jnp.where(restart, na, ona)
    na = jnp.where(restart, jnp.zeros_like(na), na)
    return {
        "out_sum_1": s1, "out_sum_2": s2, "out_sum_3": s3,
        "out_num_accumulates": na, "out_old_num_accumulates": ona,
        "out_num_updates": nu,
    }


@register_op(
    "proximal_gd", inputs=["Param", "Grad", "LearningRate"],
    outputs=["ParamOut"], no_grad=True)
def proximal_gd(ctx, attrs, Param, Grad, LearningRate):
    """Proximal gradient descent (reference
    ``optimizers/proximal_gd_op.cc``): prox_param = p - lr*g, then the
    soft-threshold / shrinkage step with l1 and l2."""
    l1 = jnp.asarray(attrs.get("l1", 0.0), Param.dtype)
    l2 = jnp.asarray(attrs.get("l2", 0.0), Param.dtype)
    lr = _lr(LearningRate, Param.dtype)
    prox = Param - lr * Grad
    shrink = jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
    return jnp.sign(prox) * shrink / (1.0 + lr * l2)


@register_op(
    "proximal_adagrad",
    inputs=["Param", "Moment", "Grad", "LearningRate"],
    outputs=["ParamOut", "MomentOut"], no_grad=True)
def proximal_adagrad(ctx, attrs, Param, Moment, Grad, LearningRate):
    """Proximal Adagrad (reference ``optimizers/proximal_adagrad_op.cc``):
    accumulate squared grads, take the proximal step with the
    per-element adaptive lr."""
    l1 = jnp.asarray(attrs.get("l1", 0.0), Param.dtype)
    l2 = jnp.asarray(attrs.get("l2", 0.0), Param.dtype)
    lr = _lr(LearningRate, Param.dtype)
    m = Moment + Grad * Grad
    # adaptive lr drives the gradient step; the shrinkage uses the PLAIN
    # scalar lr (proximal_adagrad_op.h: prox_param - lr*l1 thresholds,
    # 1/(1+lr*l2) decay)
    prox = Param - (lr / jnp.sqrt(m)) * Grad
    shrink = jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
    return jnp.sign(prox) * shrink / (1.0 + lr * l2), m
