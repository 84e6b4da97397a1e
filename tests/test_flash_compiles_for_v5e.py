"""The flash kernels at their default geometry, compiled for a described
TPU v5e (no chip attached): what Mosaic refuses (a tile that does not fit
VMEM, a block it cannot lay out) fails here and not on the chip. Nothing
runs, so nothing here is a time or a result. One file, one fixture: the
worker that is given this file is the only one that loads the TPU's
compiler."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.kernels import flash_attention as fa
from deeplearning4j_tpu.kernels._dispatch import FlashBlocks, flash_block_sizes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (batch, heads, queries, keys, head width[, value width]), dtype, causal,
# key mask, and optionally the caller's own blocks, environment, a mask of
# pairs, and a window
_CALLS = {
    "gpt2_small.train_s1024": ((16, 12, 1024, 1024, 64), "bfloat16", True,
                               False),
    "long_context_4096": ((2, 12, 4096, 4096, 64), "bfloat16", True, False),
    # CCA's call: 8 query heads of 128, k and v repeated to them
    "zaya1_8b.train_s4096": ((2, 8, 4096, 4096, 128), "bfloat16", True,
                             False),
    # indexed attention's call: 32 query heads of 128, k and v repeated to
    # them, one [batch, T, S] int8 mask of the selected pairs for all heads
    "keye_vl2_30b_a3b.train_s8192": ((2, 32, 8192, 8192, 128), "bfloat16",
                                     True, False, None, None, True),
    # a windowed layer's call: 28 query heads of 128, k and v repeated to
    # them, the last 4,096 keys of each query's past
    "smallthinker_21b_a3b.train_s16384": ((1, 28, 16384, 16384, 128),
                                          "bfloat16", True, False, None,
                                          None, False, 4096),
    "windowed_ragged_float32_pair_masked": ((2, 4, 3000, 3000, 64),
                                            "float32", True, True, None,
                                            None, True, 1000),
    "pair_masked_ragged_float32": ((2, 4, 1000, 1000, 64), "float32", True,
                                   True, None, None, True),
    "bert_like_masked_float32": ((2, 8, 2048, 2048, 128), "float32", False,
                                 True),
    "causal_masked_float32_128_wide": ((2, 8, 2048, 2048, 128), "float32",
                                       True, True),
    "float32_products_128_wide": ((2, 8, 2048, 2048, 128), "float32", True,
                                  True, None, {"DL4J_TPU_FLASH_FP32": "1"}),
    "ragged_1000_masked": ((2, 8, 1000, 1000, 64), "bfloat16", True, True),
    "cross_512_by_2048": ((2, 8, 512, 2048, 64), "bfloat16", True, False),
    "wide_heads_float32": ((2, 4, 2048, 2048, 256), "float32", True, False),
    # latent attention's call: 32 heads whose q and k are 192 wide over v
    # 128 wide, at the tile the widest operand sets, 1024 x 1024
    "kanana2_30b_a3b.train_s8192": ((2, 32, 8192, 8192, 192, 128),
                                    "bfloat16", True, False),
    "unequal_widths_float32_windowed_pair_masked": (
        (2, 4, 3000, 3000, 192, 128), "float32", True, True, None, None,
        True, 1000),
    "unequal_widths_not_causal_masked": ((2, 4, 2048, 2048, 192, 128),
                                         "bfloat16", False, True),
    # parallel/sequence.py::ulysses_attention: the whole sequence, a
    # quarter of the heads, its own 256 x 256 blocks, the gathered mask
    "ulysses_local_call": ((2, 3, 4096, 4096, 64), "bfloat16", True, True,
                           (256, 256)),
    # the same call left to the default geometry, not causal
    "whole_sequence_not_causal_masked": ((2, 3, 4096, 4096, 64), "bfloat16",
                                         False, True),
}


@pytest.mark.parametrize("call", sorted(_CALLS))
def test_forward_and_backward_compile_at_the_default_geometry(
        call, one_chip, as_on_tpu, monkeypatch):
    (b, h, t, s, d, *dv), dtype, causal, masked, *rest = _CALLS[call]
    dv = dv[0] if dv else d
    own_blocks, env, pairs, window = rest + [None, None, False, None][len(rest):]
    for name, value in (env or {}).items():
        monkeypatch.setenv(name, value)
    blocks = flash_block_sizes(t, s, max(d, dv), causal)
    if own_blocks:
        blocks = FlashBlocks(*[own_blocks] * 3)
    blocks = FlashBlocks(*[fa._clamp_blocks(bq, bk, t, s)
                           for bq, bk in blocks])
    q = jax.ShapeDtypeStruct((b, h, t, d), dtype, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, h, s, d), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, h, s, dv), dtype, sharding=one_chip)
    args = (q, k, v)
    if masked:
        args += (jax.ShapeDtypeStruct((b, s), jnp.float32,
                                      sharding=one_chip),)
    if pairs:
        args += (jax.ShapeDtypeStruct((b, t, s), jnp.int8,
                                      sharding=one_chip),)

    def loss(q, k, v, *masks):
        masks = list(masks)
        key_mask = masks.pop(0) if masked else None
        pair_mask = masks.pop(0) if pairs else None
        out = fa._flash(q, k, v, key_mask, causal, d ** -0.5, blocks,
                        window, pair_mask)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("block", [1024, 512, 256, 128])
def test_the_gpt2_call_compiles_at_each_split_the_rule_can_return(
        block, one_chip, as_on_tpu):
    """``gpt2_small.train_s1024``'s call with all three kernels at one
    block size: a diagonal tile is cut into ``block // _SUB_BLOCK`` row
    sub-blocks, each a copy of the body in the kernel's code, down to one
    (the tile whole)."""
    plan = fa.TilePlan(1024, 1024, block, block, True)
    assert plan.sub_blocks == max(1, block // fa._SUB_BLOCK)
    q = jax.ShapeDtypeStruct((16, 12, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        out = fa._flash(q, k, v, None, True, 0.125,
                        FlashBlocks(*[(block, block)] * 3), None)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_the_gpt2_head_writes_its_logits_and_no_other_array_of_their_size(
        one_chip, as_on_tpu):
    """A count of the compiled program, not a time: the one-layer
    ``gpt2_small`` step at the published head widths (16 x 1,024 positions,
    768 wide, 50,257 classes). At most two top-level operations write an
    array of rows x vocabulary elements (the logits, and their gradient
    where the compiler does not take it into the products that read it),
    none of them in float32, none of them log-probabilities, and the head
    bias's gradient has no pass of its own."""
    from deeplearning4j_tpu.models.gpt import gpt2_small
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.updaters import Adam

    rows, seq, vocabulary = 16, 1024, 50257
    trainer = Trainer(gpt2_small(
        num_layers=1, dropout=0.0, attention_dropout=0.0,
        net=NeuralNetConfiguration(updater=Adam(lr=1e-4),
                                   mixed_precision=True, rng_impl="rbg")))

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    batch = {"features": {"token_ids": jax.ShapeDtypeStruct(
        (rows, seq), jnp.int32)}}
    text = jax.jit(trainer._raw_step, **trainer._jit_kwargs).lower(
        described(jax.eval_shape(lambda: trainer.init_state(seed=0))),
        described(batch)).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    least = rows * (seq - 1) * vocabulary
    large = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if m is None or m.group(3) in ("get-tuple-element", "bitcast",
                                       "parameter", "tuple"):
            continue
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", m.group(2)):
            if math.prod(int(d) for d in dims.split(",")) >= least:
                large.append((m.group(1), dtype, line))
    assert 1 <= len(large) <= 2, [name for name, _, _ in large]
    assert all(dtype == "bf16" for _, dtype, _ in large), large
    assert not any("log_softmax" in line for _, _, line in large)
    # the bias's gradient: no top-level reduction over the rows to [50257]
    assert not re.search(
        r'= \w+\[50257\]\S* fusion\(.*op_name="[^"]*head[^"]*/reduce_sum"', entry)


def test_the_keye_cells_expert_layer_compiles_in_pieces(one_chip,
                                                        monkeypatch):
    """A count of the compiled program, not a time: one ``RoutedExperts``
    layer at ``keye_vl2_30b_a3b.train_s8192``'s shape (2 x 8,192 tokens of
    2,048, top-8 of 128 experts, 16 held, 768 wide), forward and gradient
    under ``jax.checkpoint`` as the model holds it. The 131,072 sorted
    pairs are four pieces of 32,768 rows: megablox's kernels take a
    piece's rows, and no array of the experts' inner width or of the
    model's has a row for each of the pairs."""
    from deeplearning4j_tpu.nn.layers import moe

    monkeypatch.setattr(moe, "use_pallas", lambda: True)
    monkeypatch.setattr(moe, "interpret", lambda: False)
    tokens, hidden, units, fan = 2 * 8192, 2048, 768, 8
    layer = moe.RoutedExperts(experts_total=128,
                              experts_held=tuple(range(16)), units=units,
                              top_k=fan, router="linear")
    pairs = tokens * fan
    rows = moe._piece_rows(pairs, 16, 128)
    assert (rows, -(-pairs // rows)) == (32768, 4)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    @jax.checkpoint
    def sublayer(p, x):
        y, routed = layer.apply(p, {}, x)
        return x + y, routed["pieces_run"]

    def loss(p, x):
        y, ran = sublayer(p, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32))), ran

    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), (hidden,), jnp.bfloat16)[0])
    x = jax.ShapeDtypeStruct((2, 8192, hidden), jnp.bfloat16)
    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)).lower(
        described(params), described(x)).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all(f"[{rows}," in line for line in kernels)
    assert not [line for line in kernels if f"[{pairs}," in line]
    assert f"[{pairs},{units}]" not in text
    assert f"[{pairs},{hidden}]" not in text
    # no number of a pair is looked up by index (PR 38): what gathers and
    # scatters of numbers are left take a handful a group, none an array
    # of a piece's rows, of all the pairs or of a token's experts
    numbers = re.findall(r"= \w+\[(\d+)\]\S* (?:gather|scatter)\(", text)
    assert numbers and max(map(int, numbers)) < 1024
    assert not re.search(rf"\[{tokens},128\]\S* (?:gather|scatter)\(", text)
    # the parent's layer planned 2.66 GB here (PERF.md section 6, PR 34)
    assert compiled.memory_analysis().peak_memory_in_bytes < 2.5e9


# rows of a piece, tokens, width, the most rows of one token, dtype
_PIECE_SUMS = {
    "smallthinker_21b_a3b.train_s16384": (24576, 16384, 2560, 6, "bfloat16"),
    "keye_vl2_30b_a3b.train_s8192": (32768, 16384, 2048, 8, "bfloat16"),
    "ragged_float32": (1000, 3000, 640, 3, "float32"),
}


@pytest.mark.parametrize("name", sorted(_PIECE_SUMS))
def test_the_sum_of_a_pieces_rows_compiles(name, one_chip, monkeypatch):
    """``RoutedExperts``' sum of a piece's rows into their tokens where
    pieces are walked: a sort, one row gather and the kernel
    ``segment_rows_sum`` at its default tiles, and no scatter."""
    from deeplearning4j_tpu.nn.layers import moe

    monkeypatch.setattr(moe, "use_pallas", lambda: True)
    monkeypatch.setattr(moe, "interpret", lambda: False)
    rows, count, width, most, dtype = _PIECE_SUMS[name]
    compiled = jax.jit(
        lambda r, s: moe._sum_by_token(r, s, None, count, most)).lower(
        jax.ShapeDtypeStruct((rows, width), jnp.dtype(dtype),
                             sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "segment_rows_sum" in text and " scatter(" not in text
    # the rows in token order and nothing else of their size
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2 * (
        rows + 256) * width * jnp.dtype(dtype).itemsize
