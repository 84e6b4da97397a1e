"""Transfer learning tests.

ref strategy: deeplearning4j-core TransferLearning*Test — surgery on a
trained net, frozen-prefix fine-tune, weight carry-over, nOutReplace.
The hard assertions: frozen params stay BIT-identical through fine-tuning,
the new head actually learns, and Adam moments of frozen layers stay zero
(gradients were masked before the updater, not after).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.lenet import lenet
from deeplearning4j_tpu.nn.layers import OutputLayer
from deeplearning4j_tpu.train.trainer import Trainer
from deeplearning4j_tpu.train.transfer import (
    FineTuneConfiguration,
    TransferLearning,
    TransferLearningHelper,
)
from deeplearning4j_tpu.train.updaters import Adam


def _tiny_batch(n=16, num_classes=5, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, 28, 28, 1)).astype(np.float32)
    y = np.eye(num_classes, dtype=np.float32)[np.arange(n) % num_classes]
    return {"features": jnp.asarray(x), "labels": jnp.asarray(y)}


@pytest.fixture(scope="module")
def pretrained():
    """A briefly-trained LeNet standing in for a zoo checkpoint."""
    model = lenet()
    trainer = Trainer(model)
    ts = trainer.init_state(seed=0)
    r = np.random.default_rng(1)
    batch = {
        "features": jnp.asarray(r.normal(size=(16, 28, 28, 1)).astype(np.float32)),
        "labels": jnp.asarray(np.eye(10, dtype=np.float32)[np.arange(16) % 10]),
    }
    for _ in range(3):
        ts, _ = trainer.train_step(ts, batch)
    return model, {"params": jax.device_get(ts.params),
                   "state": jax.device_get(ts.model_state)}


def _surgery(model, variables, num_classes=5):
    feature_boundary = model.layer_names[-2]  # dense under the old head
    tl = (TransferLearning(model, variables)
          .fine_tune_configuration(FineTuneConfiguration(updater=Adam(1e-2)))
          .set_feature_extractor(feature_boundary)
          .remove_last_layers(1)
          .add_layer(OutputLayer(units=num_classes, activation="softmax",
                                 loss="mcxent")))
    return tl.build(seed=7)


class TestTransferLearningBuilder:
    def test_weights_carry_over(self, pretrained):
        model, variables = pretrained
        new_model, new_vars, frozen = _surgery(model, variables)
        # every retained layer's params are the pretrained values, verbatim
        for name in new_model.layer_names[:-1]:
            if name not in variables["params"]:
                continue
            old = variables["params"][name]
            new = new_vars["params"][name]
            for k in old:
                np.testing.assert_array_equal(np.asarray(old[k]),
                                              np.asarray(new[k]))
        # the fresh head exists with the new width
        head = new_vars["params"][new_model.layer_names[-1]]
        assert head["W"].shape[-1] == 5

    def test_frozen_list_covers_prefix(self, pretrained):
        model, variables = pretrained
        new_model, _, frozen = _surgery(model, variables)
        # all parameterized layers up to and incl. the boundary are frozen
        assert frozen  # non-empty
        boundary = len(new_model.layer_names) - 2
        for name in frozen:
            assert new_model.layer_names.index(name) <= boundary
        assert new_model.layer_names[-1] not in frozen

    def test_fine_tune_config_overrides(self, pretrained):
        model, variables = pretrained
        new_model, _, _ = _surgery(model, variables)
        assert isinstance(new_model.net.updater, Adam)
        assert float(new_model.net.updater.lr) == pytest.approx(1e-2)

    def test_n_out_replace(self, pretrained):
        model, variables = pretrained
        tl = TransferLearning(model, variables)
        tl.n_out_replace(model.layer_names[-1], 3)
        new_model, new_vars, _ = tl.build(seed=3)
        head = new_vars["params"][new_model.layer_names[-1]]
        assert head["W"].shape[-1] == 3


class TestFrozenFineTune:
    def test_frozen_backbone_fine_tune(self, pretrained):
        """The round-1 'done' bar: frozen layers bit-identical, head learns,
        frozen Adam moments stay exactly zero."""
        model, variables = pretrained
        new_model, new_vars, frozen = _surgery(model, variables)

        trainer = Trainer(new_model, frozen_layers=frozen)
        ts = trainer.init_state(variables=new_vars)
        before = jax.device_get(ts.params)

        batch = _tiny_batch()
        losses = []
        for _ in range(30):
            ts, metrics = trainer.train_step(ts, batch)
            losses.append(float(jax.device_get(metrics["total_loss"])))

        after = jax.device_get(ts.params)

        # 1. frozen layers: BIT-identical
        for name in frozen:
            for k in before[name]:
                np.testing.assert_array_equal(
                    np.asarray(before[name][k]), np.asarray(after[name][k]),
                    err_msg=f"frozen layer {name}/{k} moved")

        # 2. the head learned: loss dropped substantially on the fixed batch
        assert losses[-1] < losses[0] * 0.7, losses

        # 3. head params actually moved
        head = new_model.layer_names[-1]
        assert any(
            not np.array_equal(np.asarray(before[head][k]),
                               np.asarray(after[head][k]))
            for k in before[head])

        # 4. Adam moments of frozen layers are exactly zero (grads masked
        #    BEFORE the updater, so no moment leakage)
        opt = jax.device_get(ts.opt_state)
        for moment in ("m", "v"):
            for name in frozen:
                for k, v in opt[moment][name].items():
                    assert not np.any(np.asarray(v)), \
                        f"Adam {moment} of frozen {name}/{k} non-zero"
        # and the head's second moment is non-zero (it did train)
        assert any(np.any(np.asarray(v)) for v in opt["v"][head].values())


class TestTransferLearningHelper:
    def test_featurize_matches_full_forward(self, pretrained):
        model, variables = pretrained
        boundary = model.layer_names[-3]
        helper = TransferLearningHelper(model, variables, boundary)
        x = _tiny_batch(n=4)["features"]

        feats = helper.featurize(x)
        tail, tail_vars = helper.unfrozen_graph()
        tail_out, _ = tail.apply(tail_vars, feats, up_to=len(tail.layers) - 1)

        full_out, _ = model.apply(variables, x, up_to=len(model.layers) - 1)
        np.testing.assert_allclose(np.asarray(tail_out), np.asarray(full_out),
                                   rtol=1e-5, atol=1e-5)


# --- GraphTransferLearning (round 3: ComputationGraph transfer path) --------


class TestGraphTransferLearning:
    def _tiny_graph(self):
        """input -> conv -> pool -> dense -> output (as a DAG)."""
        import jax

        from deeplearning4j_tpu.nn import layers as L
        from deeplearning4j_tpu.nn.config import (
            GraphConfig,
            GraphVertex,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.model import GraphModel

        v = {
            "conv": GraphVertex(kind="layer", inputs=["input"],
                                layer=L.Conv2D(filters=4, kernel=3,
                                               activation="relu")),
            "pool": GraphVertex(kind="layer", inputs=["conv"],
                                layer=L.GlobalPooling()),
            "dense": GraphVertex(kind="layer", inputs=["pool"],
                                 layer=L.Dense(units=8, activation="relu")),
            "output": GraphVertex(kind="layer", inputs=["dense"],
                                  layer=L.OutputLayer(units=10)),
        }
        cfg = GraphConfig(net=NeuralNetConfiguration(seed=0),
                          inputs=["input"],
                          input_shapes={"input": (8, 8, 3)},
                          vertices=v, outputs=["output"])
        m = GraphModel(cfg)
        return m, m.init()

    def test_nout_replace_and_freeze(self):
        import numpy as np

        from deeplearning4j_tpu.train.transfer import (
            FineTuneConfiguration,
            GraphTransferLearning,
        )
        from deeplearning4j_tpu.train.updaters import Adam

        model, variables = self._tiny_graph()
        gtl = (GraphTransferLearning(model, variables)
               .fine_tune_configuration(FineTuneConfiguration(updater=Adam(1e-3)))
               .set_feature_extractor("dense")
               .n_out_replace("output", 5))
        new_model, new_vars, frozen = gtl.build()
        assert frozen == ["conv", "dense"]
        # carried weights are identical; replaced head is fresh 5-wide
        np.testing.assert_array_equal(
            np.asarray(new_vars["params"]["conv"]["W"]),
            np.asarray(variables["params"]["conv"]["W"]))
        assert new_vars["params"]["output"]["W"].shape == (8, 5)
        out = new_model.output(new_vars, np.zeros((2, 8, 8, 3), np.float32))
        assert out["output"].shape == (2, 5)

    def test_frozen_training_keeps_backbone(self):
        import numpy as np

        from deeplearning4j_tpu.train.trainer import Trainer
        from deeplearning4j_tpu.train.transfer import GraphTransferLearning
        from deeplearning4j_tpu.train.updaters import Adam

        model, variables = self._tiny_graph()
        gtl = (GraphTransferLearning(model, variables)
               .set_feature_extractor("dense")
               .n_out_replace("output", 3))
        new_model, new_vars, frozen = gtl.build()
        new_model.net.updater = Adam(1e-2)
        # snapshot BEFORE training: train_step donates the state buffers
        conv_before = np.asarray(new_vars["params"]["conv"]["W"]).copy()
        head_before = np.asarray(new_vars["params"]["output"]["W"]).copy()
        tr = Trainer(new_model, frozen_layers=frozen)
        ts = tr.init_state(variables=new_vars)
        r = np.random.default_rng(0)
        batch = {"features": r.normal(size=(8, 8, 8, 3)).astype(np.float32),
                 "labels": np.eye(3, dtype=np.float32)[r.integers(0, 3, 8)]}
        for _ in range(5):
            ts, m = tr.train_step(ts, batch)
        after = tr.variables(ts)["params"]
        np.testing.assert_array_equal(np.asarray(after["conv"]["W"]),
                                      conv_before)
        assert not np.allclose(np.asarray(after["output"]["W"]), head_before)

    def test_remove_vertex_and_add_new_head(self):
        import numpy as np

        from deeplearning4j_tpu.nn import layers as L
        from deeplearning4j_tpu.nn.config import GraphVertex
        from deeplearning4j_tpu.train.transfer import GraphTransferLearning

        model, variables = self._tiny_graph()
        gtl = (GraphTransferLearning(model, variables)
               .remove_vertex("dense")  # drops dense AND output
               .add_vertex("newhead", GraphVertex(
                   kind="layer", inputs=["pool"],
                   layer=L.OutputLayer(units=2)))
               .set_outputs("newhead"))
        new_model, new_vars, _ = gtl.build()
        out = new_model.output(new_vars, np.zeros((2, 8, 8, 3), np.float32))
        assert out["newhead"].shape == (2, 2)

    def test_zoo_resnet_surgery(self):
        """The reference's canonical use: re-head a zoo ResNet."""
        import numpy as np

        from deeplearning4j_tpu.models.zoo import resnet50
        from deeplearning4j_tpu.train.transfer import GraphTransferLearning

        model = resnet50(num_classes=10, input_shape=(32, 32, 3))
        variables = model.init(seed=0)
        gtl = (GraphTransferLearning(model, variables)
               .set_feature_extractor("avgpool")
               .n_out_replace("output", 4))
        new_model, new_vars, frozen = gtl.build()
        assert "avgpool" not in frozen  # pooling has no params
        assert "output" not in frozen  # the fresh head is trainable
        assert len(frozen) > 30  # every conv/bn vertex upstream
        out = new_model.output(new_vars, np.zeros((1, 32, 32, 3), np.float32))
        assert out["output"].shape == (1, 4)


    def test_nout_replace_midgraph_reinitializes_downstream(self):
        """nOutReplace on a non-terminal vertex: downstream vertices whose
        input width changed must re-init, not carry stale-shaped weights
        (DL4J's nOutReplace nIn rule; r3 review)."""
        import numpy as np

        from deeplearning4j_tpu.train.transfer import GraphTransferLearning

        model, variables = self._tiny_graph()
        gtl = GraphTransferLearning(model, variables).n_out_replace("dense", 16)
        new_model, new_vars, _ = gtl.build()
        assert new_vars["params"]["dense"]["W"].shape == (4, 16)
        assert new_vars["params"]["output"]["W"].shape == (16, 10)
        out = new_model.output(new_vars, np.zeros((2, 8, 8, 3), np.float32))
        assert out["output"].shape == (2, 10)

    def test_remove_vertex_validation_leaves_builder_intact(self):
        from deeplearning4j_tpu.train.transfer import GraphTransferLearning

        model, variables = self._tiny_graph()
        gtl = GraphTransferLearning(model, variables)
        with pytest.raises(ValueError, match="missing inputs"):
            gtl.remove_vertex("dense", and_descendants=False)
        # builder unchanged: a valid edit still works
        assert "dense" in gtl._vertices
        new_model, new_vars, _ = gtl.n_out_replace("output", 2).build()
        assert new_vars["params"]["output"]["W"].shape[-1] == 2


    def test_build_requires_outputs(self):
        from deeplearning4j_tpu.train.transfer import GraphTransferLearning

        model, variables = self._tiny_graph()
        gtl = GraphTransferLearning(model, variables).remove_vertex("dense")
        with pytest.raises(ValueError, match="no outputs"):
            gtl.build()


def test_sequential_remove_all_layers_raises(pretrained):
    model, variables = pretrained
    tl = TransferLearning(model, variables).remove_last_layers(
        len(model.layers))
    with pytest.raises(ValueError, match="no layers"):
        tl.build()
