"""Tests for the data/RL tail readers:
Arrow IPC reader (pyarrow-written files decoded by the dependency-free
reader), GeoJSON point reader + coordinate transforms, and the ALE-style
frame-stack connector."""

import json

import numpy as np
import pytest

from deeplearning4j_tpu.data.arrow import (ArrowRecordReader,
                                           read_arrow_file,
                                           read_arrow_stream)
from deeplearning4j_tpu.data.geo import (CoordinatesDistanceTransform,
                                         GeoJsonPointReader,
                                         IPAddressToCoordinatesTransform,
                                         haversine_m, parse_point)
from deeplearning4j_tpu.data.transform import Schema, TransformProcess
from deeplearning4j_tpu.rl.history import (FrameStackEnv, HistoryProcessor,
                                           SyntheticFrameEnv,
                                           resize_bilinear, to_grayscale)

# pyarrow is only the GROUND-TRUTH WRITER for the Arrow decoder tests; the
# geo/transform/RL tests below must keep running without it, so the skip is
# scoped to this fixture rather than the module.
pa = None
try:
    import pyarrow as pa  # noqa: N816
except ImportError:
    pass

needs_pyarrow = pytest.mark.skipif(
    pa is None, reason="pyarrow (oracle writer) unavailable")


# ---------------------------------------------------------------------------
# Arrow: the hand-written decoder vs pyarrow-written ground truth
# ---------------------------------------------------------------------------

def _write_table(path, table):
    import pyarrow.ipc

    with pa.ipc.new_file(path, table.schema) as w:
        w.write_table(table)


@needs_pyarrow
def test_arrow_file_primitives(tmp_path):
    t = pa.table({
        "i32": pa.array([1, -2, 3], pa.int32()),
        "i64": pa.array([10, 20, 30], pa.int64()),
        "u8": pa.array([0, 128, 255], pa.uint8()),
        "f32": pa.array([1.5, -2.5, 0.0], pa.float32()),
        "f64": pa.array([1e-8, 2.0, -3.25], pa.float64()),
        "b": pa.array([True, False, True]),
        "s": pa.array(["alpha", "", "γamma"]),
    })
    p = tmp_path / "t.arrow"
    _write_table(p, t)

    cols = read_arrow_file(p)
    assert set(cols) == {"i32", "i64", "u8", "f32", "f64", "b", "s"}
    np.testing.assert_array_equal(cols["i32"], [1, -2, 3])
    assert cols["i32"].dtype == np.int32
    np.testing.assert_array_equal(cols["i64"], [10, 20, 30])
    np.testing.assert_array_equal(cols["u8"], [0, 128, 255])
    assert cols["u8"].dtype == np.uint8
    np.testing.assert_allclose(cols["f32"], [1.5, -2.5, 0.0])
    np.testing.assert_allclose(cols["f64"], [1e-8, 2.0, -3.25])
    np.testing.assert_array_equal(cols["b"], [True, False, True])
    assert list(cols["s"]) == ["alpha", "", "γamma"]


@needs_pyarrow
def test_arrow_multiple_batches_and_nulls(tmp_path):
    import pyarrow.ipc

    schema = pa.schema([("x", pa.float64()), ("name", pa.string())])
    p = tmp_path / "m.arrow"
    with pa.ipc.new_file(p, schema) as w:
        w.write_batch(pa.record_batch(
            [pa.array([1.0, None]), pa.array(["a", None])], schema=schema))
        w.write_batch(pa.record_batch(
            [pa.array([3.0]), pa.array(["c"])], schema=schema))
    cols = read_arrow_file(p)
    assert len(cols["x"]) == 3
    assert cols["x"][0] == 1.0 and np.isnan(cols["x"][1]) and cols["x"][2] == 3.0
    assert list(cols["name"]) == ["a", None, "c"]


@needs_pyarrow
def test_arrow_stream_roundtrip():
    import pyarrow.ipc

    t = pa.table({"a": pa.array(np.arange(100, dtype=np.int64)),
                  "b": pa.array(np.linspace(0, 1, 100))})
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    cols = read_arrow_stream(sink.getvalue().to_pybytes())
    np.testing.assert_array_equal(cols["a"], np.arange(100))
    np.testing.assert_allclose(cols["b"], np.linspace(0, 1, 100))


@needs_pyarrow
def test_arrow_record_reader_and_pyarrow_path_agree(tmp_path):
    t = pa.table({"x": pa.array([1.0, 2.0]), "y": pa.array(["u", "v"])})
    p = tmp_path / "r.arrow"
    _write_table(p, t)

    rr = ArrowRecordReader().initialize(p)
    assert rr.column_names == ["x", "y"]
    rows = list(rr)
    assert rows[0][0] == 1.0 and rows[0][1] == "u"
    assert rows[1][0] == 2.0 and rows[1][1] == "v"
    rr.reset()
    assert rr.has_next()

    via_pa = ArrowRecordReader(use_pyarrow=True).initialize(p)
    assert [list(map(str, r)) for r in via_pa] == \
        [list(map(str, r)) for r in rows]


@needs_pyarrow
def test_arrow_unsupported_types_raise(tmp_path):
    t = pa.table({"l": pa.array([[1, 2], [3]], pa.list_(pa.int32()))})
    p = tmp_path / "l.arrow"
    _write_table(p, t)
    with pytest.raises(ValueError, match="unsupported"):
        read_arrow_file(p)
    with pytest.raises(ValueError, match="magic"):
        bad = tmp_path / "bad.arrow"
        bad.write_bytes(b"not arrow")
        read_arrow_file(bad)


# ---------------------------------------------------------------------------
# Geo
# ---------------------------------------------------------------------------

def test_parse_point_and_haversine():
    assert parse_point("48.85:2.35") == [48.85, 2.35]
    assert parse_point([1, 2.5]) == [1.0, 2.5]
    # Paris -> London ≈ 344 km
    d = haversine_m(48.8566, 2.3522, 51.5074, -0.1278)
    assert 330_000 < d < 350_000
    assert haversine_m(10.0, 20.0, 10.0, 20.0) == 0.0


def test_coordinates_distance_transform():
    schema = (Schema().add_string_column("a").add_string_column("b"))
    records = [["0:0", "3:4"], ["1:1", "1:1"]]
    tp = TransformProcess(schema).add(
        CoordinatesDistanceTransform("dist", "a", "b"))
    out = tp.execute(records)
    assert out[0][-1] == pytest.approx(5.0)
    assert out[1][-1] == 0.0
    assert tp.final_schema.names()[-1] == "dist"

    hav = CoordinatesDistanceTransform("d", "a", "b", metric="haversine")
    got = hav.apply([["48.8566:2.3522", "51.5074:-0.1278"]], schema)
    assert 330_000 < got[0][-1] < 350_000


def test_geoip_transform_refuses_clearly():
    schema = Schema().add_string_column("ip")
    t = IPAddressToCoordinatesTransform("ip")
    with pytest.raises(RuntimeError, match="MaxMind"):
        t.apply([["8.8.8.8"]], schema)


def test_geojson_point_reader(tmp_path):
    doc = {
        "type": "FeatureCollection",
        "features": [
            {"type": "Feature",
             "geometry": {"type": "Point", "coordinates": [2.35, 48.85]},
             "properties": {"name": "paris", "pop": "2M"}},
            {"type": "Feature",
             "geometry": {"type": "LineString",
                          "coordinates": [[0, 0], [1, 1]]},
             "properties": {"name": "skipme"}},
            {"type": "Feature",
             "geometry": {"type": "Point", "coordinates": [-0.13, 51.51]},
             "properties": {"name": "london"}},
        ],
    }
    p = tmp_path / "pts.geojson"
    p.write_text(json.dumps(doc))
    rd = GeoJsonPointReader().initialize(p)
    rows = list(rd)
    assert len(rows) == 2  # line skipped
    assert rows[0][:2] == [2.35, 48.85]
    assert rows[0][2] == "paris" and rows[0][3] == "2M"
    assert rows[1][2] == "london" and rows[1][3] is None
    assert rd.schema().names() == ["lon", "lat", "name", "pop"]

    with pytest.raises(ValueError, match="non-Point"):
        GeoJsonPointReader(strict=True).initialize(p)


# ---------------------------------------------------------------------------
# ALE-style connector
# ---------------------------------------------------------------------------

def test_grayscale_and_resize():
    rgb = np.zeros((4, 4, 3), np.uint8)
    rgb[..., 1] = 255  # pure green
    g = to_grayscale(rgb)
    np.testing.assert_allclose(g, 0.587 * 255, rtol=1e-6)
    # constant image stays constant under resize
    r = resize_bilinear(np.full((30, 40), 7.0), (84, 84))
    assert r.shape == (84, 84)
    np.testing.assert_allclose(r, 7.0, rtol=1e-6)
    # upscale of a gradient stays monotone along the gradient axis
    grad = np.tile(np.arange(10.0), (10, 1))
    up = resize_bilinear(grad, (20, 20))
    assert (np.diff(up, axis=1) >= -1e-6).all()


def test_history_processor_stack_order():
    hp = HistoryProcessor(stack=3, size=(8, 8), scale=1.0)
    hp.add(np.full((16, 16), 1.0))
    h = hp.history()
    assert h.shape == (3, 8, 8)
    np.testing.assert_allclose(h[0], 0.0)   # zero-padded oldest
    np.testing.assert_allclose(h[2], 1.0)   # newest last
    hp.add(np.full((16, 16), 2.0))
    hp.add(np.full((16, 16), 3.0))
    hp.add(np.full((16, 16), 4.0))          # rolls the 1.0 frame out
    h = hp.history()
    np.testing.assert_allclose(h[:, 0, 0], [2.0, 3.0, 4.0])
    hp.reset()
    with pytest.raises(RuntimeError):
        hp.history()


def test_frame_stack_env_episode():
    env = FrameStackEnv(SyntheticFrameEnv(episode_len=10),
                        stack=4, skip=4, size=(84, 84))
    obs = env.reset()
    assert obs.shape == (4, 84, 84)
    assert obs.dtype == np.float32
    assert 0.0 <= obs.min() and obs.max() <= 1.0
    total, steps = 0.0, 0
    done = False
    while not done:
        obs, r, done, _ = env.step(1)
        total += r
        steps += 1
        assert obs.shape == (4, 84, 84)
    # skip=4 over a 10-step episode → 3 agent steps; rewards accumulated
    assert steps == 3
    assert total > 0


def test_frame_stack_env_feeds_dqn_shapes():
    # the connector's observation is directly consumable as a flat feature
    env = FrameStackEnv(SyntheticFrameEnv(), stack=2, skip=2, size=(10, 10))
    obs = env.reset()
    flat = obs.reshape(-1)
    assert flat.shape == (200,)
