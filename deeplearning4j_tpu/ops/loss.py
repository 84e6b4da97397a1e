"""Loss op namespace (↔ org.nd4j.linalg.lossfunctions + NDLoss).

ref: nd4j LossFunctions.LossFunction enum and the ILossFunction impls
(LossMCXENT, LossNegativeLogLikelihood, LossMSE, LossL1/L2, LossBinaryXENT,
LossHinge, LossSquaredHinge, LossKLD, LossPoisson, LossCosineProximity,
LossHuber, LossMAPE, LossMSLE, LossMixtureDensity, LossFMeasure, CTC …).

Conventions (matching the reference):
- ``labels`` are one-hot/dense targets with the same trailing shape as
  predictions unless noted; sparse-label variants take integer class ids.
- every loss returns per-example values reduced with ``reduction``
  ('mean' | 'sum' | 'none'); weights broadcast per-example or per-output.
- classification losses operate on *pre-activation* logits where possible
  (fused log-softmax — numerically stable, XLA-fusable), unlike the
  reference which post-processes activations; probability-input variants are
  provided for parity.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

LOSS_REGISTRY = {}


def register_loss(name):
    def deco(fn):
        LOSS_REGISTRY[name.lower()] = fn
        return fn

    return deco


def get_loss(name: str):
    try:
        return LOSS_REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown loss '{name}'; available: {sorted(LOSS_REGISTRY)}"
        ) from None


def _reduce(val, reduction, weights=None):
    if weights is not None:
        val = val * weights
    if reduction == "mean":
        if weights is not None:
            return jnp.sum(val) / jnp.maximum(jnp.sum(weights), 1e-12)
        return jnp.mean(val)
    if reduction == "sum":
        return jnp.sum(val)
    if reduction == "none":
        return val
    raise ValueError(f"unknown reduction {reduction}")


@register_loss("mcxent")
@register_loss("softmax_cross_entropy")
def softmax_cross_entropy(logits, labels, weights=None, reduction="mean", label_smoothing=0.0):
    """ref: LossMCXENT (multi-class cross-entropy vs one-hot labels)."""
    if label_smoothing > 0.0:
        k = logits.shape[-1]
        labels = labels * (1.0 - label_smoothing) + label_smoothing / k
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.sum(labels * logp, axis=-1)
    return _reduce(ce, reduction, weights)


@register_loss("negativeloglikelihood")
@register_loss("nll")
def negative_log_likelihood(logits, labels, weights=None, reduction="mean"):
    """ref: LossNegativeLogLikelihood — identical math to MCXENT here."""
    return softmax_cross_entropy(logits, labels, weights, reduction)


@register_loss("sparse_softmax_cross_entropy")
def sparse_softmax_cross_entropy(logits, label_ids, weights=None, reduction="mean"):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, label_ids[..., None], axis=-1)[..., 0]
    return _reduce(ce, reduction, weights)


def linear_softmax_cross_entropy(hidden, weight, labels, bias=None):
    """Per-position cross entropy of ``softmax(hidden @ weight.T + bias)``
    at ``labels``, in float32: the loss of a softmax over a linear layer's
    output at a language model's vocabulary, as one operation with its own
    backward rule. Its value and gradients are those of
    ``sparse_softmax_cross_entropy(einsum("...h,vh->...v", hidden, weight)
    + bias, labels, reduction="none")``; what the rule leaves out is work:
    no ``[rows, vocabulary]`` array of log-probabilities, no pass of its
    own for the bias's gradient, and the logits' gradient in a form the
    compiler can take into the two products that read it.

    hidden [..., H], weight [V, H] and bias [V] in the compute dtype, in
    which the logits are held; labels [...] integer. The row statistic
    (``logsumexp``) and the result are float32."""
    from deeplearning4j_tpu.observability.flightrecorder import record_event

    record_event(
        "head.linear_cross_entropy", rows=math.prod(labels.shape),
        vocabulary=weight.shape[0],
        logits_dtype=str(jnp.result_type(hidden, weight)),
        bias=bias is not None, logsumexp="max_then_sum_float32")
    return _linear_cross_entropy(hidden, weight, bias, labels)


@jax.custom_vjp
def _linear_cross_entropy(hidden, weight, bias, labels):
    return _linear_cross_entropy_fwd(hidden, weight, bias, labels)[0]


def _linear_cross_entropy_fwd(hidden, weight, bias, labels):
    logits = jnp.einsum("...h,vh->...v", hidden, weight,
                        preferred_element_type=jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    # written once, in the compute dtype; every later pass reads this array
    logits = logits.astype(jnp.result_type(hidden, weight))
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    at_label = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (lse - at_label.astype(jnp.float32),
            (hidden, weight, bias, logits, lse, labels))


def _linear_cross_entropy_bwd(residuals, g):
    hidden, weight, bias, logits, lse, labels = residuals
    classes = jax.lax.broadcasted_iota(labels.dtype, logits.shape,
                                       logits.ndim - 1)
    d_logits = ((jnp.exp(logits.astype(jnp.float32) - lse[..., None])
                 - (classes == labels[..., None])) * g[..., None]
                ).astype(logits.dtype)
    d_hidden = jnp.einsum("...v,vh->...h", d_logits, weight).astype(
        hidden.dtype)
    if bias is None:
        d_weight = jnp.einsum("...v,...h->vh", d_logits, hidden)
        return d_hidden, d_weight.astype(weight.dtype), None, None
    # the bias's gradient is the logits' summed over the rows: a column of
    # ones beside the hidden state takes that sum in the weight's product
    ones = jnp.ones(hidden.shape[:-1] + (1,), hidden.dtype)
    d_both = jnp.einsum("...v,...h->vh", d_logits,
                        jnp.concatenate([hidden, ones], axis=-1))
    return (d_hidden, d_both[:, :-1].astype(weight.dtype),
            d_both[:, -1].astype(bias.dtype), None)


_linear_cross_entropy.defvjp(_linear_cross_entropy_fwd,
                             _linear_cross_entropy_bwd)


@register_loss("xent")
@register_loss("binary_cross_entropy")
def binary_cross_entropy(logits, labels, weights=None, reduction="mean", eps=1e-7):
    """ref: LossBinaryXENT. Input is logits (sigmoid fused, stable)."""
    ce = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    ce = jnp.sum(ce, axis=-1)
    return _reduce(ce, reduction, weights)


@register_loss("binary_cross_entropy_probs")
def binary_cross_entropy_probs(probs, labels, weights=None, reduction="mean", eps=1e-7):
    p = jnp.clip(probs, eps, 1.0 - eps)
    ce = -(labels * jnp.log(p) + (1.0 - labels) * jnp.log1p(-p))
    return _reduce(jnp.sum(ce, axis=-1), reduction, weights)


@register_loss("mse")
def mse(pred, target, weights=None, reduction="mean"):
    """ref: LossMSE — mean over output dims per example."""
    v = jnp.mean(jnp.square(pred - target), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("mae")
@register_loss("l1_mean")
def mae(pred, target, weights=None, reduction="mean"):
    v = jnp.mean(jnp.abs(pred - target), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("l1")
def l1(pred, target, weights=None, reduction="mean"):
    v = jnp.sum(jnp.abs(pred - target), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("l2")
def l2(pred, target, weights=None, reduction="mean"):
    v = jnp.sum(jnp.square(pred - target), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("rmse")
def rmse(pred, target, weights=None, reduction="mean"):
    return jnp.sqrt(mse(pred, target, weights, reduction))


@register_loss("msle")
def msle(pred, target, weights=None, reduction="mean", eps=1e-7):
    v = jnp.mean(jnp.square(jnp.log1p(jnp.maximum(pred, eps)) - jnp.log1p(jnp.maximum(target, eps))), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("mape")
def mape(pred, target, weights=None, reduction="mean", eps=1e-7):
    v = jnp.mean(jnp.abs((target - pred) / jnp.maximum(jnp.abs(target), eps)), axis=-1) * 100.0
    return _reduce(v, reduction, weights)


@register_loss("hinge")
def hinge(pred, target, weights=None, reduction="mean"):
    """ref: LossHinge. target in {-1, +1} (or {0,1} → mapped)."""
    t = jnp.where(target > 0, 1.0, -1.0)
    v = jnp.sum(jnp.maximum(0.0, 1.0 - t * pred), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("capsnet_margin")
@register_loss("margin")
def margin(pred, target, weights=None, reduction="mean",
           m_plus=0.9, m_minus=0.1, lam=0.5):
    """CapsNet margin loss (Sabour 2017, the CapsuleStrength objective):
    L_c = T_c·max(0, m+ − ‖v_c‖)² + λ(1−T_c)·max(0, ‖v_c‖ − m−)².
    ``pred`` holds capsule strengths (‖v_c‖ ∈ [0,1]); target one-hot."""
    present = target * jnp.square(jnp.maximum(0.0, m_plus - pred))
    absent = lam * (1.0 - target) * jnp.square(
        jnp.maximum(0.0, pred - m_minus))
    v = jnp.sum(present + absent, axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("squared_hinge")
def squared_hinge(pred, target, weights=None, reduction="mean"):
    t = jnp.where(target > 0, 1.0, -1.0)
    v = jnp.sum(jnp.square(jnp.maximum(0.0, 1.0 - t * pred)), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("kl_divergence")
@register_loss("kld")
def kl_divergence(pred_probs, target_probs, weights=None, reduction="mean", eps=1e-7):
    p = jnp.clip(target_probs, eps, 1.0)
    q = jnp.clip(pred_probs, eps, 1.0)
    v = jnp.sum(p * (jnp.log(p) - jnp.log(q)), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("poisson")
def poisson(pred, target, weights=None, reduction="mean", eps=1e-7):
    v = jnp.sum(pred - target * jnp.log(jnp.maximum(pred, eps)), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("cosine_proximity")
def cosine_proximity(pred, target, weights=None, reduction="mean", eps=1e-12):
    pn = pred / jnp.maximum(jnp.linalg.norm(pred, axis=-1, keepdims=True), eps)
    tn = target / jnp.maximum(jnp.linalg.norm(target, axis=-1, keepdims=True), eps)
    v = -jnp.sum(pn * tn, axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("huber")
def huber(pred, target, weights=None, reduction="mean", delta=1.0):
    d = pred - target
    abs_d = jnp.abs(d)
    quad = jnp.minimum(abs_d, delta)
    v = jnp.sum(0.5 * quad**2 + delta * (abs_d - quad), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("log_cosh")
def log_cosh(pred, target, weights=None, reduction="mean"):
    d = pred - target
    v = jnp.sum(d + jax.nn.softplus(-2.0 * d) - jnp.log(2.0), axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("wasserstein")
def wasserstein(pred, target, weights=None, reduction="mean"):
    """ref: LossWasserstein (critic loss: mean(pred * target))."""
    v = jnp.mean(pred * target, axis=-1)
    return _reduce(v, reduction, weights)


@register_loss("fmeasure")
def fmeasure(pred, target, weights=None, reduction="mean", beta=1.0):
    """ref: LossFMeasure — differentiable soft-F_beta on probabilities.

    Computed over the whole batch (the reference computes a batch-global
    score); reduction arg kept for interface uniformity.
    """
    tp = jnp.sum(pred * target)
    fp = jnp.sum(pred * (1.0 - target))
    fn = jnp.sum((1.0 - pred) * target)
    b2 = beta * beta
    f = ((1 + b2) * tp) / jnp.maximum((1 + b2) * tp + b2 * fn + fp, 1e-12)
    return 1.0 - f


def ctc_loss(logits, logit_lengths, labels, label_lengths, blank_id=0, reduction="mean"):
    """CTC loss (ref: libnd4j ctc_loss op / LossCTC).

    logits: [N, T, C]; labels: [N, S] int32 padded with anything past length.
    Log-domain forward algorithm via lax.scan over time.
    """
    from jax import lax

    n, t, c = logits.shape
    s = labels.shape[1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    # Extended label seq with blanks: length 2S+1
    ext = jnp.full((n, 2 * s + 1), blank_id, dtype=labels.dtype)
    ext = ext.at[:, 1::2].set(labels)
    ext_len = 2 * label_lengths + 1

    neg_inf = -1e30
    # alpha init: positions 0 (blank) and 1 (first label)
    alpha0 = jnp.full((n, 2 * s + 1), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[:, 0, blank_id])
    first_lab = jnp.take_along_axis(logp[:, 0, :], ext[:, 1:2], axis=-1)[:, 0]
    alpha0 = alpha0.at[:, 1].set(jnp.where(label_lengths > 0, first_lab, neg_inf))

    same_as_prev2 = jnp.concatenate(
        [jnp.ones((n, 2), dtype=bool), ext[:, 2:] == ext[:, :-2]], axis=1
    )

    def logaddexp(a, b):
        return jnp.logaddexp(a, b)

    def step(alpha, lp_t):
        # lp_t: [N, C] log-probs at time t
        shift1 = jnp.concatenate([jnp.full((n, 1), neg_inf), alpha[:, :-1]], axis=1)
        shift2 = jnp.concatenate([jnp.full((n, 2), neg_inf), alpha[:, :-2]], axis=1)
        shift2 = jnp.where(same_as_prev2, neg_inf, shift2)
        new = logaddexp(alpha, logaddexp(shift1, shift2))
        emit = jnp.take_along_axis(lp_t, ext, axis=-1)
        return new + emit, None

    lps = jnp.swapaxes(logp, 0, 1)[1:]  # [T-1, N, C]; t=0 is in alpha0

    def masked_step(carry, lp_t):
        alpha, t_idx = carry
        new, _ = step(alpha, lp_t)
        keep = (t_idx < logit_lengths)[:, None]  # freeze alpha past seq end
        alpha = jnp.where(keep, new, alpha)
        return (alpha, t_idx + 1), None

    (alpha_f, _), _ = lax.scan(masked_step, (alpha0, jnp.ones((), jnp.int32)), lps)
    idx_last = jnp.maximum(ext_len - 1, 0)
    idx_prev = jnp.maximum(ext_len - 2, 0)
    a_last = jnp.take_along_axis(alpha_f, idx_last[:, None], axis=1)[:, 0]
    a_prev = jnp.take_along_axis(alpha_f, idx_prev[:, None], axis=1)[:, 0]
    # Empty label sequence (ext_len == 1): only the all-blank path exists —
    # don't logaddexp alpha[0] with itself.
    ll = jnp.where(ext_len > 1, jnp.logaddexp(a_last, a_prev), a_last)
    loss = -ll
    return _reduce(loss, reduction)


LOSS_REGISTRY["ctc"] = ctc_loss


def l2_regularization(params_tree, coeff):
    """ref: org.nd4j.linalg.learning.regularization.L2Regularization."""
    leaves = jax.tree_util.tree_leaves(params_tree)
    return coeff * sum(jnp.sum(jnp.square(p)) for p in leaves)


def l1_regularization(params_tree, coeff):
    leaves = jax.tree_util.tree_leaves(params_tree)
    return coeff * sum(jnp.sum(jnp.abs(p)) for p in leaves)
