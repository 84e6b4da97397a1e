"""``ops/loss.py::linear_softmax_cross_entropy``: the language-model head's
loss as one operation with its own backward rule. Its value and every
gradient against autodiff through the plain form (``einsum`` +
``sparse_softmax_cross_entropy``), the two models' training losses
against what they returned before they called it, and the trainer's
gradient accumulation over it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.gpt import gpt_tiny
from deeplearning4j_tpu.models.zaya import zaya_tiny
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.ops import loss as losses
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.updaters import Sgd

BATCH_SHAPES = {"3x17": (3, 17), "flat_51": (51,), "2x3x5": (2, 3, 5)}


def _inputs(batch_shape, classes, width=24, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    hidden = jax.random.normal(k[0], batch_shape + (width,)).astype(dtype)
    weight = (0.3 * jax.random.normal(k[1], (classes, width))).astype(dtype)
    bias = jax.random.normal(k[2], (classes,)).astype(dtype)
    labels = jax.random.randint(k[3], batch_shape, 0, classes)
    # position weights with zeros among them, as a padding mask gives
    position = (jax.random.uniform(k[4], batch_shape) > 0.3).astype(
        jnp.float32) * jax.random.uniform(k[0], batch_shape)
    return hidden, weight, bias, labels, position


def _plain(hidden, weight, bias, labels):
    logits = jnp.einsum("...h,vh->...v", hidden, weight)
    if bias is not None:
        logits = logits + bias
    return losses.sparse_softmax_cross_entropy(logits, labels,
                                               reduction="none")


def _weighted(per_position, hidden, weight, bias, labels, position):
    each = per_position(hidden, weight, bias, labels).astype(jnp.float32)
    return jnp.sum(each * position) / jnp.sum(position)


def _value_and_grads(per_position, hidden, weight, bias, labels, position):
    wrt = (0, 1) if bias is None else (0, 1, 2)
    return jax.value_and_grad(
        lambda h, w, b: _weighted(per_position, h, w, b, labels, position),
        argnums=wrt)(hidden, weight, bias)


def _operation(hidden, weight, bias, labels):
    return losses.linear_softmax_cross_entropy(hidden, weight, labels, bias)


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("classes", [1001, 7])
@pytest.mark.parametrize("shape", sorted(BATCH_SHAPES))
def test_value_and_gradients_equal_autodiff_through_the_plain_form(
        shape, classes, with_bias):
    hidden, weight, bias, labels, position = _inputs(BATCH_SHAPES[shape],
                                                     classes)
    if not with_bias:
        bias = None
    per_position = _operation(hidden, weight, bias, labels)
    assert per_position.dtype == jnp.float32
    assert per_position.shape == labels.shape
    np.testing.assert_allclose(per_position,
                               _plain(hidden, weight, bias, labels),
                               rtol=1e-5, atol=1e-5)
    want = _value_and_grads(_plain, hidden, weight, bias, labels, position)
    got = jax.jit(lambda *a: _value_and_grads(_operation, *a))(
        hidden, weight, bias, labels, position)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-6 * float(jnp.abs(w).max()) + 1e-9)


def test_a_position_of_weight_zero_gives_no_gradient():
    hidden, weight, bias, labels, position = _inputs((3, 17), 1001)
    position = position.at[1].set(0.0)
    d_hidden = _value_and_grads(_operation, hidden, weight, bias, labels,
                                position)[1][0]
    assert not np.any(np.asarray(d_hidden[1]))
    assert np.any(np.asarray(d_hidden[0]))


def test_large_logits_do_not_overflow():
    hidden, weight, bias, labels, position = _inputs((3, 17), 1001)
    hidden = 200.0 * hidden  # logits of some thousands: exp overflows bare
    want = _value_and_grads(_plain, hidden, weight, bias, labels, position)
    got = _value_and_grads(_operation, hidden, weight, bias, labels,
                           position)
    assert np.isfinite(got[0])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "no_bias"])
def test_bf16_inputs_keep_the_statistic_in_float32(with_bias):
    """Against the plain form in float32 over the same bf16 values: the
    logits are held in bf16 (three decimal digits), the statistic and the
    result in float32. A ``logsumexp`` rounded to bf16, or a gradient
    without its label term, is ten times outside these tolerances."""
    hidden, weight, bias, labels, position = _inputs(
        (3, 17), 1001, dtype=jnp.bfloat16)
    if not with_bias:
        bias = None
    got = _value_and_grads(_operation, hidden, weight, bias, labels, position)
    up = [None if a is None else a.astype(jnp.float32)
          for a in (hidden, weight, bias)]
    want = _value_and_grads(_plain, *up, labels, position)
    assert got[0].dtype == jnp.float32
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3)
    for g, w in zip(got[1], want[1]):
        assert g.dtype == jnp.bfloat16
        gap = jnp.linalg.norm(g.astype(jnp.float32) - w) / jnp.linalg.norm(w)
        assert float(gap) < 1e-2
    # the label term alone is most of the hidden state's gradient here
    no_label = jax.grad(lambda h: jnp.sum(jax.nn.logsumexp(
        jnp.einsum("...h,vh->...v", h, up[1])
        + (0.0 if bias is None else up[2]), axis=-1)
        * position) / jnp.sum(position))(up[0])
    assert float(jnp.linalg.norm(no_label - want[1][0])
                 / jnp.linalg.norm(want[1][0])) > 0.1


# -- the models' training losses, as they were before they called it ----------

def _gpt_before(model, params, state, batch, rng=None):
    features = batch["features"]
    if not isinstance(features, dict):
        features = {"token_ids": features}
    ids = features["token_ids"]
    mask = features.get("mask")
    h = model.encode(params, ids, train=True, rng=rng, mask=mask)
    lg = model.logits(params, h)[:, :-1]
    labels = batch.get("labels")
    if labels is None:
        labels = ids[:, 1:]
    w = (jnp.ones(labels.shape, jnp.float32) if mask is None
         else mask[:, 1:].astype(jnp.float32))
    per_tok = losses.sparse_softmax_cross_entropy(lg, labels,
                                                  reduction="none")
    return jnp.sum(per_tok * w) / jnp.maximum(jnp.sum(w), 1.0)


def _zaya_before(model, params, state, batch, rng=None):
    ids = batch["features"]["token_ids"]
    h, _ = model.encode(params, ids)
    lg = model.logits(params, h)[:, :-1]
    return jnp.mean(losses.sparse_softmax_cross_entropy(
        lg, ids[:, 1:], reduction="none").astype(jnp.float32))


def _ids(vocab, n=4, t=16, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (n, t)).astype(
        np.int32)


def _gpt_batches():
    ids = _ids(128)
    mask = np.ones(ids.shape, np.float32)
    mask[0, 5:] = 0.0
    mask[2] = 0.0
    return {
        "ids_only": {"features": {"token_ids": ids}},
        "bare_features": {"features": ids},
        "padding_mask": {"features": {"token_ids": ids, "mask": mask}},
        "labels_given": {"features": {"token_ids": ids},
                         "labels": _ids(128, t=15, seed=1)},
        "labels_and_mask": {"features": {"token_ids": ids, "mask": mask},
                            "labels": _ids(128, t=15, seed=1)},
    }


def _same_loss_and_leaves(model, before, batch, seed):
    params = model.init(seed)["params"]
    # a head bias that is not zero, where the model has one
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jnp.arange(a.size, dtype=a.dtype).reshape(
            a.shape) / a.size if "out_b" in jax.tree_util.keystr(path) else a,
        params)
    want = jax.value_and_grad(
        lambda p: before(model, p, {}, batch))(params)
    got = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, {}, batch)[0]))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    flat_got = jax.tree_util.tree_leaves_with_path(got[1])
    flat_want = jax.tree_util.tree_leaves(want[1])
    assert len(flat_got) == len(flat_want)
    # the key bias's gradient is zero but for float noise (softmax is
    # shift invariant), so the floor is set from all the leaves
    floor = 2e-6 * max(float(jnp.abs(w).max()) for w in flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=floor,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("case", sorted(_gpt_batches()))
def test_gpt_loss_fn_is_what_it_was(case):
    _same_loss_and_leaves(gpt_tiny(), _gpt_before, _gpt_batches()[case], 3)


@pytest.mark.parametrize("held", [(0, 1, 2, 3), (1, 3)],
                         ids=["all_held", "half_held"])
def test_zaya_loss_fn_is_what_it_was(held):
    batch = {"features": {"token_ids": _ids(96, n=2)}}
    _same_loss_and_leaves(zaya_tiny(experts_held=held), _zaya_before,
                          batch, 5)


def test_the_loss_metric_and_weight_of_gpt_are_unchanged():
    model = gpt_tiny()
    params = model.init(0)["params"]
    batch = _gpt_batches()["padding_mask"]
    loss, (state, metrics) = model.loss_fn(params, {}, batch)
    assert state == {} and set(metrics) == {"loss"}
    assert float(metrics["loss"]) == float(loss)
    assert float(model.loss_weight(batch)) == float(
        batch["features"]["mask"][:, 1:].sum())


@pytest.mark.parametrize("masked", [False, True], ids=["whole", "masked"])
def test_grad_accum_of_two_equals_one_step_over_the_whole_batch(masked):
    model = gpt_tiny(net=NeuralNetConfiguration(updater=Sgd(0.1)))
    whole, halves = Trainer(model), Trainer(model, grad_accum=2)
    ts1, ts2 = whole.init_state(), halves.init_state()
    features = {"token_ids": _ids(128, n=8)}
    if masked:
        mask = np.ones((8, 16), np.float32)
        mask[:4, 3:] = 0.0  # the first half nearly all padding
        features["mask"] = mask
    batch = {"features": features}
    for _ in range(2):
        ts1, m1 = whole.train_step(ts1, batch)
        ts2, m2 = halves.train_step(ts2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ts1.params),
                    jax.tree_util.tree_leaves(ts2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-6)
