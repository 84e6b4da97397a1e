"""Training UI server tests (SURVEY §2.7 Training UI).

The server must list runs, serve scalar series parsed from BOTH storage
formats the listeners write (JSONL and TB event files), and render the
dashboard page — all verified over real HTTP against a live instance.
"""

import json
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.train.tensorboard import TensorBoardWriter
from deeplearning4j_tpu.train.ui import UIServer


@pytest.fixture()
def ui(tmp_path):
    # run 1: JSONL metrics
    with open(tmp_path / "run1.jsonl", "w") as fh:
        for step in range(5):
            fh.write(json.dumps({"step": step, "epoch": 0,
                                 "total_loss": 2.0 - 0.3 * step,
                                 "note": "non-numeric ignored"}) + "\n")
    # run 2: TB event files
    w = TensorBoardWriter(str(tmp_path / "run2"))
    for step in range(4):
        w.add_scalar("loss", 1.0 - 0.1 * step, step)
        w.add_scalar("acc", 0.5 + 0.1 * step, step)
    w.close()

    server = UIServer(str(tmp_path), port=0).start()
    yield server
    server.stop()


def _get(server, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}{path}", timeout=10) as r:
        return r.status, r.read()


class TestUIServer:
    def test_dashboard_page(self, ui):
        status, body = _get(ui, "/")
        assert status == 200
        assert b"training UI" in body and b"/api/metrics" in body

    def test_runs_listing(self, ui):
        status, body = _get(ui, "/api/runs")
        assert status == 200
        assert json.loads(body) == ["run1.jsonl", "run2"]

    def test_jsonl_metrics(self, ui):
        _, body = _get(ui, "/api/metrics?run=run1.jsonl")
        series = json.loads(body)
        assert "total_loss" in series and "note" not in series
        pts = series["total_loss"]
        assert pts[0] == [0, 2.0]
        assert pts[-1][0] == 4
        assert pts[-1][1] == pytest.approx(0.8)

    def test_tb_metrics_parsed_by_own_reader(self, ui):
        _, body = _get(ui, "/api/metrics?run=run2")
        series = json.loads(body)
        assert set(series) == {"loss", "acc"}
        np.testing.assert_allclose(
            [v for _, v in series["loss"]],
            [1.0, 0.9, 0.8, 0.7], rtol=1e-6)
        assert [s for s, _ in series["acc"]] == [0, 1, 2, 3]

    def test_unknown_run_empty(self, ui):
        _, body = _get(ui, "/api/metrics?run=nope")
        assert json.loads(body) == {}

    def test_path_traversal_refused(self, ui):
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ui, "/api/metrics?run=../etc")
        assert ei.value.code == 400

    def test_404(self, ui):
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ui, "/nope")
        assert ei.value.code == 404


def test_remote_stats_routing(tmp_path):
    """↔ RemoteUIStatsStorageRouter: listener on the 'training host' POSTs
    metric records; the UI server's run/metrics API charts them."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.train.ui import RemoteStatsListener, UIServer

    server = UIServer(str(tmp_path), port=0).start()
    try:
        url = f"http://127.0.0.1:{server.port}"
        lis = RemoteStatsListener(url, "remote-run", flush_every=2)
        for step in range(5):
            lis.on_iteration(0, step, None,
                             {"total_loss": jnp.asarray(1.0 / (step + 1))})
        lis.on_fit_end(None, None)
        assert lis.last_error is None, lis.last_error
        assert "remote-run.jsonl" in server.runs()
        series = server.metrics("remote-run.jsonl")
        assert len(series["total_loss"]) == 5
        assert series["total_loss"][0][1] == 1.0
    finally:
        server.stop()


def test_remote_stats_post_rejects_bad_run(tmp_path):
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.train.ui import UIServer

    server = UIServer(str(tmp_path), port=0).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/api/post?run=../evil",
            data=b'{"step": 1}\n')
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(req, timeout=2)
    finally:
        server.stop()


def test_remote_stats_listener_survives_dead_server(tmp_path):
    from deeplearning4j_tpu.train.ui import RemoteStatsListener

    lis = RemoteStatsListener("http://127.0.0.1:9", "r", flush_every=1,
                              timeout=0.5)
    lis.on_iteration(0, 0, None, {"total_loss": 1.0})  # must not raise
    assert lis.last_error is not None


def test_remote_stats_listener_through_trainer_fit(tmp_path):
    """The listener rides a real Trainer.fit loop (protocol compliance)."""
    import numpy as np

    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.train.trainer import Trainer
    from deeplearning4j_tpu.train.ui import RemoteStatsListener, UIServer

    server = UIServer(str(tmp_path), port=0).start()
    try:
        url = f"http://127.0.0.1:{server.port}"
        lis = RemoteStatsListener(url, "fit-run", flush_every=4)
        model = lenet()
        tr = Trainer(model)
        ts = tr.init_state()
        r = np.random.default_rng(0)
        x = r.normal(size=(16, 28, 28, 1)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[r.integers(0, 10, 16)]
        tr.fit(ts, ArrayDataSetIterator(x, y, batch_size=8), epochs=2,
               listeners=[lis])
        assert lis.last_error is None, lis.last_error
        series = server.metrics("fit-run.jsonl")
        assert len(series["total_loss"]) >= 4
    finally:
        server.stop()


def test_remote_stats_requeues_on_failure(tmp_path):
    """A failed flush keeps the records and delivers them once the server
    is reachable (the router's queue-don't-drop contract)."""
    from deeplearning4j_tpu.train.ui import RemoteStatsListener, UIServer

    server = UIServer(str(tmp_path), port=0).start()
    port = server.port
    server.stop()  # now unreachable
    lis = RemoteStatsListener(f"http://127.0.0.1:{port}", "q", flush_every=1,
                              timeout=0.5)
    lis.on_iteration(0, 0, None, {"total_loss": 3.0})
    assert lis.last_error is not None and lis._buf  # queued, not dropped
    server2 = UIServer(str(tmp_path), port=port).start()
    try:
        lis.on_iteration(0, 1, None, {"total_loss": 2.0})
        series = server2.metrics("q.jsonl")
        assert len(series["total_loss"]) == 2  # both records arrived
    finally:
        server2.stop()


def test_health_page_without_engine(tmp_path):
    """/health renders even with no SLO engine published: the live
    default-registry scrape plus a no-engine notice."""
    from deeplearning4j_tpu.observability import metrics as om
    from deeplearning4j_tpu.observability import slo

    om.reset_default_registry()
    slo.set_default_engine(None)
    server = UIServer(str(tmp_path), port=0).start()
    try:
        om.get_training_metrics().steps_total.inc(5)
        status, body = _get(server, "/health")
        assert status == 200
        assert b"no SLO engine running" in body
        assert b"train_steps_total 5" in body  # live scrape on the page
        status, body = _get(server, "/api/health")
        assert status == 200
        doc = json.loads(body)
        assert doc["slo"] is None
        names = {m["name"] for m in doc["metrics"]["metrics"]}
        assert "train_steps_total" in names
    finally:
        server.stop()
        om.reset_default_registry()


def test_health_page_renders_slo_states(tmp_path):
    """With a published engine, /health shows per-rule alert states —
    the zero-install dashboard answers "is training healthy?"."""
    from deeplearning4j_tpu.observability import metrics as om
    from deeplearning4j_tpu.observability import slo
    from deeplearning4j_tpu.serving.metrics import ServingMetrics

    om.reset_default_registry()
    sm = ServingMetrics()
    rule = slo.SLORule(
        name="ui-avail", kind="availability", objective=0.9,
        total=slo.Selector("serving_requests_total"),
        bad=slo.Selector("serving_requests_total",
                         match=(("code", "5.."),)),
        windows=(slo.BurnWindow(10.0, 40.0, 1.0),),
        for_s=0.0, resolve_hold_s=10.0)
    clock = [0.0]
    engine = slo.HealthEngine([rule], registries=[sm.registry],
                              interval_s=1.0, clock=lambda: clock[0],
                              snapshot_every_s=0)
    engine.tick()
    slo.set_default_engine(engine)
    server = UIServer(str(tmp_path), port=0).start()
    try:
        status, body = _get(server, "/health")
        assert status == 200
        assert b"ui-avail" in body and b">OK<" in body
        # drive the rule to firing; the page reflects it live
        for t in (1.0, 2.0):
            clock[0] = t
            sm.requests_total.inc(20, model="m", code="500")
            engine.tick()
        status, body = _get(server, "/health")
        assert b"FIRING" in body
        doc = json.loads(_get(server, "/api/health")[1])
        assert doc["slo"]["rules"][0]["state"] == "firing"
    finally:
        server.stop()
        slo.set_default_engine(None)
        om.reset_default_registry()
