"""What stood before the flash kernels learned a window, ``RoutedExperts``
a router input of its own and a ReLU gate, lowers to what it lowered to:
a call with no window at the three shapes the standing cells compile (the
Mosaic kernels operation for operation, their source locations apart, and
the text around them), and the tiny ``zaya`` and ``keye`` training steps.
Since PR 39 (a value narrower than the query and key, a sigmoid router and
shared experts) also ``smallthinker_21b_a3b``'s windowed call, its tiny
step and a layer that walks pieces: their digests are PR 38's tree's.
The kernels' digests were taken from the commit before the window (``python
tests/test_lowered_as_before_the_window.py`` prints them for a tree). The
two steps' and the one-piece layer's were taken again at PR 38, which
changed how every ``RoutedExperts`` looks up a pair's probability, place
and weight, on purpose and in all three models alike
(``tests/test_routed_lookup.py`` holds the results to the bit): they hold
the next change that means to leave the standing models alone."""

import base64
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.kernels import flash_attention as fa

_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')

# (batch, heads, sequence, head width), with a mask of pairs or without,
# and the window (None: none)
CALLS = {
    "gpt2_small.train_s1024": ((16, 12, 1024, 64), False, None),
    "zaya1_8b.train_s4096": ((2, 8, 4096, 128), False, None),
    "keye_vl2_30b_a3b.train_s8192": ((2, 32, 8192, 128), True, None),
    "smallthinker_21b_a3b.train_s16384": ((1, 28, 16384, 128), False, 4096),
}
BEFORE = {
    "gpt2_small.train_s1024":
        "00763092edb872c56495dde629943c9974eeb15e229dbf68d0652f5687d6429a",
    "zaya1_8b.train_s4096":
        "2f44addcac921556ba91c2de93f07961f5ab4e0eabf306b38aa58fd89ced275e",
    "keye_vl2_30b_a3b.train_s8192":
        "8225d758b1d52b2761ce145fcebb28b21d45030be45bce67d4f2b8d64c051c98",
    "smallthinker_21b_a3b.train_s16384":
        "86b8a1049ac236b279620336d5e393b5b5b263235ceec259467b841369f270bf",
    "zaya_tiny":
        "cd682af67afcd514a2cb1791aec5483f62f264d352eb32ee95bbcc59147a4252",
    "keye_tiny":
        "5879e55302dcb838f681bf17b7828b20747737347755f97f3ba5229f254593be",
    "smallthinker_tiny":
        "5c71682129edd37f2d6e12bef74b30fa515c6e672be2b294e52c7802f5d1b546",
    "layer_of_one_piece":
        "ff5bb5a0be13b4aa56ed8241f7aa5838e6efef1040a4ff5e668d0a4f2ce334af",
    "layer_of_pieces":
        "db5412a44ec6c0e91a0337263c9cb6978c82e814df3b0e9d409ce140dff034f7",
}


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def kernels_without_locations(text: str):
    """The Mosaic kernels of a lowered text, each as its MLIR with no
    source location, and the text with the kernels' bodies taken out."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    kernels = []
    for body in _BODY.findall(text):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            kernels.append(module.operation.get_asm(enable_debug_info=False))
    return kernels, _BODY.sub("BODY", text)


def call_digest(name: str) -> str:
    shape, masked, window = CALLS[name]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((shape[0], shape[2], shape[2]), jnp.int8)

    def loss(q, k, v, m):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, window=window,
            pair_mask=m if masked else None).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        q, q, q, mask).lower(lowering_platforms=("tpu",)).as_text()
    kernels, around = kernels_without_locations(text)
    assert len(kernels) == 3
    return _digest("\n".join(kernels) + around)


def step_digest(name: str) -> str:
    from deeplearning4j_tpu.models.keye import keye_tiny
    from deeplearning4j_tpu.models.smallthinker import smallthinker_tiny
    from deeplearning4j_tpu.models.zaya import zaya_tiny

    model = {"zaya_tiny": zaya_tiny, "keye_tiny": keye_tiny,
             "smallthinker_tiny": smallthinker_tiny}[name]()
    variables = model.init(0)
    batch = {"features": {"token_ids": jnp.zeros((2, 64), jnp.int32)}}
    step = jax.value_and_grad(
        lambda p: model.loss_fn(p, variables["state"], batch)[0])
    return _digest(jax.jit(step).lower(variables["params"]).as_text())


def scatter_updates(text: str):
    """The type of each ``stablehlo.scatter``'s update operand."""
    return [re.search(r"\}\) : \(([^)]*)\) -> ", text[at.end():]).group(
        1).split(", ")[-1]
            for at in re.finditer(r'"stablehlo\.scatter"\(', text)]


def layer_text(held, top_k, router):
    """One ``RoutedExperts`` layer of 16 experts over 128 tokens of 64,
    value and gradients, lowered."""
    from deeplearning4j_tpu.nn.layers.moe import RoutedExperts

    layer = RoutedExperts(experts_total=16, experts_held=tuple(range(held)),
                          units=32, router_hidden=16, top_k=top_k,
                          router=router)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), (64,), jnp.float32)[0])
    x = jax.ShapeDtypeStruct((2, 64, 64), jnp.float32)
    carried = jax.ShapeDtypeStruct((128, 16), jnp.float32)

    def loss(p, x, carried):
        y, _ = layer.apply(p, {"router": carried}, x)
        return jnp.sum(jnp.square(y))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x, carried).as_text()


def test_a_layer_of_one_piece_lowers_to_what_it_lowered_to():
    """``zaya1_8b``'s shape, tiny: 8 of 16 experts held behind the MLP
    router, one piece of all the tokens; the digest is the text's at
    PR 38, whose lookups by compare and select are this layer's too."""
    assert _digest(layer_text(8, 1, "mlp")) == BEFORE["layer_of_one_piece"]


def test_a_layer_that_walks_pieces_lowers_to_what_it_lowered_to():
    """``keye_vl2_30b_a3b``'s and ``smallthinker_21b_a3b``'s form, tiny: a
    linear router, 2 of 16 held at top-3, pieces of 96 of the 384 pairs;
    neither a sigmoid router nor shared experts (PR 39) reach it."""
    assert _digest(layer_text(2, 3, "linear")) == BEFORE["layer_of_pieces"]


def test_a_layer_that_walks_pieces_scatters_no_row():
    """2 of 16 held at top-3: pieces of 96 of the 384 pairs. No scatter of
    the lowered step, forward or backward, takes a piece's rows (or the
    tokens') as its update: what is left writes numbers, not rows."""
    text = layer_text(2, 3, "linear")
    assert "stablehlo.while" in text  # the walk over the later pieces
    updates = scatter_updates(text)
    assert updates  # the run starts' ranks
    assert not [u for u in updates if re.fullmatch(r"tensor<\d+x64x\w+>", u)]


@pytest.mark.parametrize("name", sorted(CALLS))
def test_a_standing_cells_call_lowers_to_what_it_lowered_to(name, as_on_tpu):
    """Since PR 39 v may be narrower than q and k: a call whose three
    share one width lowers to the parent's text, kernels and all."""
    assert call_digest(name) == BEFORE[name]


@pytest.mark.parametrize("name", ["zaya_tiny", "keye_tiny",
                                  "smallthinker_tiny"])
def test_a_standing_models_step_lowers_to_what_it_lowered_to(name):
    assert step_digest(name) == BEFORE[name]


def test_a_window_changes_the_kernels_and_nothing_around_them(as_on_tpu):
    q = jax.ShapeDtypeStruct((1, 4, 4096, 128), jnp.bfloat16)

    def lowered(window):
        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, causal=True, window=window).astype(jnp.float32))

        return kernels_without_locations(
            jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
                q, q, q).lower(lowering_platforms=("tpu",)).as_text())

    bare, around = lowered(None)
    windowed, around_windowed = lowered(1024)
    assert around == around_windowed  # the same operands, the same grid
    assert all(a != b for a, b in zip(bare, windowed))
    assert lowered(4096) == (bare, around)  # no shorter than the keys


if __name__ == "__main__":
    for name in ("_use_pallas", "_on_tpu"):
        setattr(fa, name, lambda: True)
    fa._interpret = lambda: False
    for name in CALLS:
        print(f'    "{name}": "{call_digest(name)}",')
    for name in ("zaya_tiny", "keye_tiny", "smallthinker_tiny"):
        print(f'    "{name}": "{step_digest(name)}",')
    print(f'    "layer_of_one_piece": "{_digest(layer_text(8, 1, "mlp"))}",')
    print(f'    "layer_of_pieces": "{_digest(layer_text(2, 3, "linear"))}",')
