"""NN op namespace (↔ org.nd4j.linalg.factory.ops.NDNN).

ref: nd4j NDNN generated namespace + libnd4j declarable nn ops
(ops/declarable/generic/nn/: softmax, layer_norm, dropout, relu family …).
All lower to XLA; fused into surrounding matmuls by the compiler rather than
hand-scheduled as in the reference's cuDNN helper path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# --- activations (ref: libnd4j transform_strict activation ops) ---

relu = jax.nn.relu
relu6 = jax.nn.relu6
sigmoid = jax.nn.sigmoid
tanh = jnp.tanh
softmax = jax.nn.softmax
log_softmax = jax.nn.log_softmax
softplus = jax.nn.softplus
soft_sign = jax.nn.soft_sign
elu = jax.nn.elu
selu = jax.nn.selu
gelu = jax.nn.gelu
silu = jax.nn.silu
swish = jax.nn.silu
hard_sigmoid = jax.nn.hard_sigmoid
hard_tanh = jax.nn.hard_tanh
leaky_relu = jax.nn.leaky_relu


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def hard_swish(x):
    return x * jax.nn.relu6(x + 3.0) / 6.0


def thresholded_relu(x, theta=1.0):
    return jnp.where(x > theta, x, 0.0)


def prelu(x, alpha):
    """ref: libnd4j prelu op (learned per-channel negative slope)."""
    return jnp.where(x >= 0, x, alpha * x)


def rational_tanh(x):
    """ref: libnd4j RationalTanh — cheap rational tanh approximation:
    1.7159 * ta(2x/3) with ta(y) = sign(y)·(1 − 1/(1+|y|+y²+1.41645·y⁴))."""
    y = 2.0 * x / 3.0
    ay = jnp.abs(y)
    ta = jnp.sign(y) * (1.0 - 1.0 / (1.0 + ay + y * y + 1.41645 * y**4))
    return 1.7159 * ta


def rectified_tanh(x):
    return jnp.maximum(0.0, jnp.tanh(x))


def gelu_tanh(x):
    return jax.nn.gelu(x, approximate=True)


def cube(x):
    return x * x * x


def swish_beta(x, beta=1.0):
    return x * jax.nn.sigmoid(beta * x)


# --- normalization (ref: libnd4j layer_norm / batchnorm / lrn ops) ---


def layer_norm(x, gamma=None, beta=None, axis=-1, eps=1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    if gamma is not None:
        y = y * gamma
    if beta is not None:
        y = y + beta
    return y


def rms_norm(x, weight, eps=1e-5):
    """x / rms(x) * weight over the last axis, the statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return (y * weight).astype(x.dtype)


def batch_norm_inference(x, mean, var, gamma, beta, eps=1e-5, channel_axis=-1):
    shape = [1] * x.ndim
    shape[channel_axis] = x.shape[channel_axis]
    mean = mean.reshape(shape)
    var = var.reshape(shape)
    scale = (gamma.reshape(shape) if gamma is not None else 1.0) * lax.rsqrt(var + eps)
    offset = (beta.reshape(shape) if beta is not None else 0.0) - mean * scale
    return x * scale + offset


def lrn(x, depth_radius=5, bias=1.0, alpha=1.0, beta=0.5):
    """Local response normalization over channel axis (NHWC).

    ref: libnd4j lrn op / DL4J LocalResponseNormalization layer.
    """
    sq = jnp.square(x)
    c = x.shape[-1]
    pad = depth_radius
    sq_pad = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(pad, pad)])
    window = jnp.stack(
        [sq_pad[..., i : i + c] for i in range(2 * pad + 1)], axis=0
    ).sum(axis=0)
    return x / jnp.power(bias + alpha * window, beta)


def l2_normalize(x, axis=-1, eps=1e-12):
    return x * lax.rsqrt(jnp.maximum(jnp.sum(jnp.square(x), axis=axis, keepdims=True), eps))


# --- dropout (ref: libnd4j dropout op; DL4J Dropout/AlphaDropout/Gaussian*) ---


def dropout(x, rate, rng, deterministic=False):
    if deterministic or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def alpha_dropout(x, rate, rng, deterministic=False):
    """ref: DL4J AlphaDropout (SELU-preserving)."""
    if deterministic or rate == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    a = (keep + alpha_p**2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    return a * jnp.where(mask, x, alpha_p) + b


def gaussian_dropout(x, rate, rng, deterministic=False):
    if deterministic or rate == 0.0:
        return x
    stddev = (rate / (1.0 - rate)) ** 0.5
    return x * (1.0 + stddev * jax.random.normal(rng, x.shape))


def gaussian_noise(x, stddev, rng, deterministic=False):
    if deterministic or stddev == 0.0:
        return x
    return x + stddev * jax.random.normal(rng, x.shape)


# --- linear / embedding ---


def linear(x, w, b=None, precision=None):
    y = jnp.matmul(x, w, precision=precision)
    if b is not None:
        y = y + b
    return y


def embedding_lookup(table, ids):
    """ref: DL4J EmbeddingLayer / EmbeddingSequenceLayer forward = gather."""
    return jnp.take(table, ids, axis=0)


# --- attention (ref: libnd4j multi_head_dot_product_attention; see also
# kernels/flash_attention.py for the Pallas blockwise version) ---


def dot_product_attention(q, k, v, mask=None, scale=None, dropout_rate=0.0, rng=None):
    """Plain O(T²) attention; q,k,v: [..., T, H] or [..., heads, T, Dh]."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = jnp.einsum("...qd,...kd->...qk", q, k) * s
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    weights = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and rng is not None:
        weights = dropout(weights, dropout_rate, rng)
    return jnp.einsum("...qk,...kd->...qd", weights, v)


# --- padding/misc ---


def pad(x, paddings, mode="constant", constant_value=0.0):
    return jnp.pad(x, paddings, mode=mode, constant_values=constant_value)


def safe_sq_norm(x, axis=-1, keepdims=True, eps=1e-8):
    """Sum-of-squares clamped to eps² — the safe-norm substrate.

    ``sqrt(safe_sq_norm(x))`` and ``x * rsqrt(safe_sq_norm(x))`` have
    finite gradients at x=0 (plain ``norm`` backprops NaN there: the
    standard JAX safe-norm pitfall). Shared by the l2norm graph vertex and
    the capsule squash/strength layers.
    """
    return jnp.maximum(jnp.sum(jnp.square(x), axis=axis, keepdims=keepdims),
                       eps * eps)
