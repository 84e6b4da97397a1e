"""Attention layers + BERT family tests.

ref patterns: oracle testing (flash kernel vs XLA reference attention),
tiny-dataset convergence sanity, config serde round-trip (SURVEY §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels.flash_attention import (
    flash_attention,
    reference_attention,
)
from deeplearning4j_tpu.models.bert import Bert, BertConfig, bert_tiny, make_mlm_batch
from deeplearning4j_tpu.nn.config import config_from_json, config_to_json
from deeplearning4j_tpu.nn.layers import (
    LearnedSelfAttention,
    SelfAttention,
    TransformerEncoderBlock,
)
from deeplearning4j_tpu.train.trainer import Trainer


def _qkv(rng, b=2, h=2, t=32, d=16):
    ks = jax.random.split(jax.random.key(rng), 3)
    shape = (b, h, t, d)
    return tuple(jax.random.normal(k, shape) for k in ks)


def test_flash_matches_reference(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    q, k, v = _qkv(0)
    got = flash_attention(q, k, v)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_causal_matches_reference(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    q, k, v = _qkv(1)
    got = flash_attention(q, k, v, causal=True)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_key_mask_matches_reference(monkeypatch):
    # In-kernel key-padding-mask path — what the BERT TPU train step uses.
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    q, k, v = _qkv(2)
    mask = jnp.ones((q.shape[0], q.shape[2])).at[:, 20:].set(0.0)
    got = flash_attention(q, k, v, key_mask=mask)
    want = reference_attention(q, k, v, key_mask=mask)
    np.testing.assert_allclose(
        np.asarray(got)[:, :, :20], np.asarray(want)[:, :, :20], atol=2e-5
    )


def test_flash_causal_key_mask_matches_reference(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    q, k, v = _qkv(3)
    mask = jnp.ones((q.shape[0], q.shape[2])).at[:, 24:].set(0.0)
    got = flash_attention(q, k, v, causal=True, key_mask=mask)
    want = reference_attention(q, k, v, causal=True, key_mask=mask)
    np.testing.assert_allclose(
        np.asarray(got)[:, :, :24], np.asarray(want)[:, :, :24], atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_key_mask_with_rows_masked_whole(monkeypatch, causal):
    """A ``key_mask`` can mask a row whole. Example 0 loses its
    first 40 keys (under ``causal`` its first 40 rows then see no key at
    all), example 1 its last 28, example 2 every key: rows masked whole
    read 0, every other row and all three gradients follow the reference."""
    monkeypatch.setenv("DL4J_TPU_FORCE_PALLAS", "1")
    t = 128
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (3, 2, t, 64)) for kk in ks)
    mask = jnp.ones((3, t)).at[0, :40].set(0.0).at[1, 100:].set(0.0)
    mask = mask.at[2].set(0.0)
    seen = np.ones((3, 1, t, 1), bool)  # rows with at least one live key
    seen[2] = False
    if causal:
        seen[0, :, :40] = False
    w = jnp.asarray(seen, jnp.float32) * jnp.cos(
        jnp.arange(3 * 2 * t * 64, dtype=jnp.float32)).reshape(3, 2, t, 64)

    def grads(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, causal=causal, key_mask=mask) * w)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    def flash(*a, **kw):
        return flash_attention(*a, block_q=32, block_k=32, **kw)

    out = np.asarray(flash(q, k, v, causal=causal, key_mask=mask))
    want = np.asarray(reference_attention(q, k, v, causal=causal,
                                          key_mask=mask))
    keep = np.broadcast_to(seen, out.shape)
    np.testing.assert_allclose(out[keep], want[keep], atol=5e-5, rtol=1e-4)
    assert not out[~keep].any()
    for g, r in zip(jax.tree_util.tree_leaves(grads(flash)),
                    jax.tree_util.tree_leaves(grads(reference_attention))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=5e-4, rtol=1e-3)


def test_self_attention_shapes_and_mask():
    layer = SelfAttention(num_heads=4, out_size=32)
    rng = jax.random.key(0)
    params, _ = layer.init(rng, (16, 32), jnp.float32)
    x = jax.random.normal(rng, (3, 16, 32))
    y, _ = layer.apply(params, {}, x)
    assert y.shape == (3, 16, 32)
    # Masked keys must not influence outputs of unmasked queries.
    mask = jnp.ones((3, 16)).at[:, 8:].set(0.0)
    y1, _ = layer.apply(params, {}, x, mask=mask)
    x2 = x.at[:, 8:, :].set(123.0)  # perturb only masked positions
    y2, _ = layer.apply(params, {}, x2, mask=mask)
    np.testing.assert_allclose(
        np.asarray(y1[:, :8]), np.asarray(y2[:, :8]), atol=1e-5
    )


def test_learned_self_attention_fixed_queries():
    layer = LearnedSelfAttention(num_heads=2, out_size=16, n_queries=4)
    params, _ = layer.init(jax.random.key(0), (20, 16), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 20, 16))
    y, _ = layer.apply(params, {}, x)
    assert y.shape == (2, 4, 16)
    assert layer.output_shape((20, 16)) == (4, 16)


def test_transformer_block_shapes():
    blk = TransformerEncoderBlock(num_heads=2, intermediate=64)
    params, _ = blk.init(jax.random.key(0), (10, 32), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 10, 32))
    y, _ = blk.apply(params, {}, x)
    assert y.shape == (2, 10, 32)


def test_bert_config_roundtrip():
    cfg = BertConfig(hidden=64, num_layers=1, num_heads=2, vocab_size=100)
    s = config_to_json(cfg)
    cfg2 = config_from_json(s)
    assert cfg2.hidden == 64 and cfg2.vocab_size == 100


# Tier-1 budget relief (the PR 6/7 pattern, paying for the PR 20
# autoscaler suite): the MLM training discipline stays wired every
# tier-1 run via test_bert_gathered_mlm_trains (same model family, the
# gathered-loss path) and the remat-grads leg; the dense-loss
# convergence run rides tier-2.
@pytest.mark.slow
def test_bert_tiny_trains():
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.train.updaters import Adam

    model = bert_tiny(max_position=32,
                      net=NeuralNetConfiguration(updater=Adam(1e-3)))
    trainer = Trainer(model)
    ts = trainer.init_state()
    batch = make_mlm_batch(0, batch_size=8, seq_len=32,
                           vocab_size=model.config.vocab_size, pad_frac=0.2)
    losses = []
    for i in range(12):
        ts, metrics = trainer.train_step(ts, batch)
        losses.append(float(jax.device_get(metrics["mlm_loss"])))
    assert losses[-1] < losses[0] * 0.9, losses
    assert np.isfinite(losses[-1])


def test_bert_forward_masked_padding_invariant():
    model = bert_tiny(max_position=16, dropout=0.0, attention_dropout=0.0)
    v = model.init(seed=0)
    batch = make_mlm_batch(1, batch_size=2, seq_len=16,
                           vocab_size=model.config.vocab_size, pad_frac=0.4)
    f = {k: jnp.asarray(a) for k, a in batch["features"].items()}
    h1, _ = model.apply(v, f)
    # garbage in padded token slots must not change unpadded outputs
    ids2 = np.array(batch["features"]["token_ids"])
    pad = np.array(batch["features"]["mask"]) == 0
    ids2[pad] = 7
    f2 = dict(f, token_ids=jnp.asarray(ids2))
    h2, _ = model.apply(v, f2)
    keep = np.array(batch["features"]["mask"]) > 0
    np.testing.assert_allclose(
        np.asarray(h1)[keep], np.asarray(h2)[keep], atol=1e-4
    )


def test_flash_attention_backend_dispatch():
    """backend param: explicit 'xla' == reference; bad value raises; auto on
    CPU (no TPU) takes the XLA path at any length (r3 dispatch policy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest

    from deeplearning4j_tpu.kernels.flash_attention import (
        flash_attention,
        reference_attention,
    )

    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(2, 2, 16, 8)), jnp.float32)
    k = jnp.asarray(r.normal(size=(2, 2, 16, 8)), jnp.float32)
    v = jnp.asarray(r.normal(size=(2, 2, 16, 8)), jnp.float32)
    np.testing.assert_allclose(
        flash_attention(q, k, v, backend="xla"),
        reference_attention(q, k, v), rtol=1e-6)
    np.testing.assert_allclose(
        flash_attention(q, k, v),  # auto, off-TPU -> xla
        reference_attention(q, k, v), rtol=1e-6)
    with pytest.raises(ValueError, match="backend"):
        flash_attention(q, k, v, backend="cuda")


def test_flash_min_seq_env_override(monkeypatch):
    from deeplearning4j_tpu.kernels import _dispatch

    monkeypatch.setenv("DL4J_TPU_FLASH_MIN_SEQ", "123")
    assert _dispatch.flash_min_seq() == 123
    monkeypatch.delenv("DL4J_TPU_FLASH_MIN_SEQ")
    assert _dispatch.flash_min_seq() == 1024


def test_transformer_block_remat_grads_match():
    # remat must change memory, not math: grads bitwise-close to non-remat
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderBlock

    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 12, 32)),
                    jnp.float32)
    blk = TransformerEncoderBlock(num_heads=4)
    blk_r = TransformerEncoderBlock(num_heads=4, remat=True)
    params, _ = blk.init(jax.random.key(0), (12, 32), jnp.float32)

    def loss(b):
        return lambda p: jnp.sum(b.apply(p, {}, x, train=False)[0] ** 2)

    g = jax.grad(loss(blk))(params)
    gr = jax.grad(loss(blk_r))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_bert_gathered_mlm_head_matches_dense():
    """Gathered (mlm_positions) and dense (mlm_mask) layouts of the SAME
    batch must produce the same loss — the gathered head only skips
    positions whose weight is zero."""
    model = bert_tiny(max_position=32, dropout=0.0, attention_dropout=0.0,
                      use_nsp=False)
    v = model.init(seed=0)
    batch = make_mlm_batch(3, batch_size=4, seq_len=32,
                           vocab_size=model.config.vocab_size,
                           max_predictions=8)
    lab = batch["labels"]
    # derive the dense view: scatter the gathered labels/weights back to [N,T]
    n, t = batch["features"]["token_ids"].shape
    dense_labels = np.zeros((n, t), np.int32)
    dense_mask = np.zeros((n, t), np.float32)
    for i in range(n):
        for j in range(lab["mlm_positions"].shape[1]):
            if lab["mlm_weights"][i, j] > 0:
                p = lab["mlm_positions"][i, j]
                dense_labels[i, p] = lab["mlm_labels"][i, j]
                dense_mask[i, p] = 1.0
    dense_batch = {"features": batch["features"],
                   "labels": {"mlm_labels": dense_labels,
                              "mlm_mask": dense_mask}}
    lg, _ = model.loss_fn(v["params"], v["state"], batch)
    ld, _ = model.loss_fn(v["params"], v["state"], dense_batch)
    np.testing.assert_allclose(float(lg), float(ld), rtol=1e-5)


def test_bert_gathered_mlm_trains():
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.train.updaters import Adam

    model = bert_tiny(max_position=32, use_nsp=True,
                      net=NeuralNetConfiguration(updater=Adam(1e-3)))
    trainer = Trainer(model)
    ts = trainer.init_state()
    batch = make_mlm_batch(0, batch_size=8, seq_len=32,
                           vocab_size=model.config.vocab_size,
                           max_predictions=8)
    losses = []
    for _ in range(12):
        ts, metrics = trainer.train_step(ts, batch)
        losses.append(float(jax.device_get(metrics["mlm_loss"])))
    assert losses[-1] < losses[0] * 0.9, losses
