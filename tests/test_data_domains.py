"""Audio / columnar / SQL data-domain tests.

Oracles: WAV files are written with the stdlib ``wave`` module and parsed
back; the spectrogram of a pure sine must peak at the right FFT bin; MFCC
frames have the declared shape; SQL results come from a real sqlite3 DB.
"""

import sqlite3
import wave

import numpy as np
import pytest

from deeplearning4j_tpu.data import (
    ColumnarRecordReader,
    SQLRecordReader,
    WavFileRecordReader,
    mel_filterbank,
    mfcc,
    read_wav,
    spectrogram,
)


def _write_wav(path, x, rate=16000, width=2, channels=1):
    with wave.open(str(path), "wb") as w:
        w.setnchannels(channels)
        w.setsampwidth(width)
        w.setframerate(rate)
        if width == 2:
            data = (np.clip(x, -1, 1) * 32767).astype("<i2")
        else:
            data = ((np.clip(x, -1, 1) * 127) + 128).astype("u1")
        if channels > 1:
            data = np.repeat(data[:, None], channels, axis=1)
        w.writeframes(data.tobytes())


class TestWav:
    def test_roundtrip_16bit(self, tmp_path):
        t = np.arange(16000) / 16000
        x = 0.5 * np.sin(2 * np.pi * 440 * t)
        p = tmp_path / "a.wav"
        _write_wav(p, x)
        y, rate = read_wav(p)
        assert rate == 16000 and y.shape == (16000,)
        np.testing.assert_allclose(y, x, atol=1e-3)

    def test_stereo_mixdown_and_8bit(self, tmp_path):
        x = np.linspace(-0.5, 0.5, 1000)
        p = tmp_path / "s.wav"
        _write_wav(p, x, width=1, channels=2)
        y, _ = read_wav(p)
        assert y.shape == (1000,)
        np.testing.assert_allclose(y, x, atol=2e-2)

    def test_sine_spectrogram_peak_bin(self):
        rate, freq, n_fft = 16000, 1000, 400
        t = np.arange(rate) / rate
        x = np.sin(2 * np.pi * freq * t).astype(np.float32)
        spec = spectrogram(x, frame_length=n_fft, hop=160)
        peak = int(np.argmax(spec.mean(axis=0)))
        assert peak == round(freq * n_fft / rate)  # bin 25

    def test_mfcc_shape_and_finite(self):
        x = np.random.default_rng(0).normal(size=8000).astype(np.float32)
        m = mfcc(x, 16000, num_coeffs=13)
        assert m.shape[1] == 13 and m.shape[0] > 10
        assert np.isfinite(m).all()

    def test_mel_filterbank_partition(self):
        fb = mel_filterbank(26, 400, 16000)
        assert fb.shape == (26, 201)
        assert (fb >= 0).all() and fb.max() <= 1.0
        # every filter has support
        assert (fb.sum(axis=1) > 0).all()

    def test_reader_with_labels(self, tmp_path):
        for name in ("cat_1.wav", "dog_1.wav"):
            _write_wav(tmp_path / name,
                       np.random.default_rng(0).normal(size=2000) * 0.1)
        rr = WavFileRecordReader(tmp_path, features="mfcc",
                                 label_fn=lambda p: p.stem.split("_")[0])
        recs = list(rr)
        assert len(recs) == 2
        feats, label = recs[0]
        assert feats.ndim == 2 and label == "cat"


class TestColumnar:
    def test_rows_view_and_matrix(self):
        rr = ColumnarRecordReader({
            "a": np.array([1.0, 2.0, 3.0]),
            "b": np.array([10, 20, 30]),
            "label": np.array(["x", "y", "x"]),
        }, schema=["a", "b", "label"])
        assert len(rr) == 3
        assert list(rr)[1] == [2.0, 20, "y"]
        m = rr.features_matrix(["a", "b"])
        np.testing.assert_allclose(m, [[1, 10], [2, 20], [3, 30]])

    def test_npz_source(self, tmp_path):
        p = tmp_path / "cols.npz"
        np.savez(p, x=np.arange(4.0), y=np.arange(4.0) ** 2)
        rr = ColumnarRecordReader(p, schema=["x", "y"])
        assert list(rr)[3] == [3.0, 9.0]

    def test_ragged_refused(self):
        with pytest.raises(ValueError, match="ragged"):
            ColumnarRecordReader({"a": [1, 2], "b": [1]})

    def test_bad_schema_refused(self):
        with pytest.raises(ValueError, match="missing"):
            ColumnarRecordReader({"a": [1]}, schema=["a", "zz"])


class TestSQL:
    def test_query_records_and_reset(self, tmp_path):
        db = str(tmp_path / "t.db")
        conn = sqlite3.connect(db)
        conn.execute("CREATE TABLE iris (sl REAL, sw REAL, species TEXT)")
        conn.executemany("INSERT INTO iris VALUES (?,?,?)",
                         [(5.1, 3.5, "setosa"), (7.0, 3.2, "versicolor"),
                          (6.3, 3.3, "virginica")])
        conn.commit()
        conn.close()

        rr = SQLRecordReader("SELECT sl, sw, species FROM iris WHERE sl > ?",
                             database=db, params=(5.5,))
        rows = list(rr)
        assert rows == [[7.0, 3.2, "versicolor"], [6.3, 3.3, "virginica"]]
        assert rr.column_names == ["sl", "sw", "species"]
        assert list(rr) == rows  # re-iterable (reset semantics)
        rr.close()

    def test_needs_database_or_conn(self):
        with pytest.raises(ValueError, match="database"):
            SQLRecordReader("SELECT 1")


class TestFrameSequence:
    def test_video_as_frame_dirs(self, tmp_path):
        from deeplearning4j_tpu.data.audio import FrameSequenceRecordReader

        r = np.random.default_rng(0)
        for vid, n in (("clipA", 4), ("clipB", 3)):
            d = tmp_path / vid
            d.mkdir()
            for i in range(n):
                np.save(d / f"frame_{i:03d}.npy",
                        r.random((8, 8, 3)).astype(np.float32))
        rr = FrameSequenceRecordReader(tmp_path, height=8, width=8,
                                       label_fn=lambda p: p.name)
        recs = list(rr)
        assert len(recs) == 2
        frames, label = recs[0]
        assert frames.shape == (4, 8, 8, 3) and label == "clipA"
        assert recs[1][0].shape == (3, 8, 8, 3)

    def test_max_frames(self, tmp_path):
        from deeplearning4j_tpu.data.audio import FrameSequenceRecordReader

        d = tmp_path / "v"
        d.mkdir()
        for i in range(6):
            np.save(d / f"f{i}.npy", np.zeros((4, 4, 3), np.float32))
        rr = FrameSequenceRecordReader(tmp_path, height=4, width=4,
                                       max_frames=2)
        assert list(rr)[0][0].shape == (2, 4, 4, 3)


class TestGymConnector:
    def test_duck_typed_gymnasium_style_env(self):
        from deeplearning4j_tpu.rl.mdp import GymEnv

        class Fake:
            class action_space:
                n = 3

            class observation_space:
                shape = (5,)

            def reset(self, seed=None):
                return np.zeros(5), {}

            def step(self, a):
                return np.ones(5), 1.0, False, True, {}

        env = GymEnv(Fake())
        assert env.action_count == 3
        assert env.observation_shape == (5,)
        obs = env.reset()
        assert obs.shape == (5,) and obs.dtype == np.float32
        obs, rew, done, info = env.step(1)
        assert done and info["truncated"] and rew == 1.0

    def test_classic_gym_four_tuple(self):
        from deeplearning4j_tpu.rl.mdp import GymEnv

        class Fake:
            def reset(self):
                return np.zeros(2)

            def step(self, a):
                return np.ones(2), 0.5, True, {"TimeLimit.truncated": True}

        env = GymEnv(Fake())
        env.reset()
        obs, rew, done, info = env.step(0)
        assert done and info["truncated"]

    def test_real_gymnasium_cartpole(self):
        pytest.importorskip("gymnasium")
        from deeplearning4j_tpu.rl.mdp import GymEnv

        env = GymEnv(name="CartPole-v1", seed=0)
        assert env.action_count == 2
        assert env.observation_shape == (4,)
        obs = env.reset()
        assert obs.shape == (4,)
        total = 0
        done = False
        while not done and total < 600:
            obs, rew, done, info = env.step(total % 2)
            total += 1
        assert done and "truncated" in info

    def test_real_gymnasium_trains_with_a3c(self):
        pytest.importorskip("gymnasium")
        from deeplearning4j_tpu.rl import A3CConfig, A3CDiscrete
        from deeplearning4j_tpu.rl.mdp import GymEnv

        agent = A3CDiscrete(
            lambda i: GymEnv(name="CartPole-v1", seed=i),
            A3CConfig(num_workers=4, n_steps=8, seed=0))
        losses = agent.train(30)
        assert np.isfinite(losses).all()
        assert agent.episode_returns  # episodes completed across workers


# --- Excel (.xlsx) reader (round 3; ↔ datavec-excel ExcelRecordReader) ------


class TestExcelReader:
    def test_roundtrip_types(self, tmp_path):
        from deeplearning4j_tpu.data.excel import ExcelRecordReader, write_xlsx

        p = tmp_path / "t.xlsx"
        write_xlsx(p, [["name", "score", "ok"],
                       ["ada", 3.5, True],
                       ["bob", 4.0, False]])
        rr = ExcelRecordReader(p, skip_rows=1)
        recs = list(rr)
        assert recs == [["ada", 3.5, True], ["bob", 4.0, False]]

    def test_sparse_rows_pad_none(self, tmp_path):
        from deeplearning4j_tpu.data.excel import ExcelRecordReader, write_xlsx

        p = tmp_path / "s.xlsx"
        write_xlsx(p, [[1.0, None, 3.0]])
        assert list(ExcelRecordReader(p)) == [[1.0, None, 3.0]]

    def test_sheet_selection_and_missing(self, tmp_path):
        from deeplearning4j_tpu.data.excel import ExcelRecordReader, write_xlsx

        p = tmp_path / "n.xlsx"
        write_xlsx(p, [[1.0]], sheet_name="data")
        assert list(ExcelRecordReader(p, sheet="data")) == [[1.0]]
        assert list(ExcelRecordReader(p, sheet=0)) == [[1.0]]
        import pytest as _p
        with _p.raises(ValueError, match="not found"):
            list(ExcelRecordReader(p, sheet="nope"))

    def test_to_dataset_bridge(self, tmp_path):
        import numpy as np

        from deeplearning4j_tpu.data import RecordReaderDataSetIterator
        from deeplearning4j_tpu.data.excel import ExcelRecordReader, write_xlsx

        p = tmp_path / "d.xlsx"
        write_xlsx(p, [[0.1, 0.2, 0.0], [0.3, 0.4, 1.0]])
        it = RecordReaderDataSetIterator(ExcelRecordReader(p), batch_size=2,
                                         num_classes=2)
        ds = next(iter(it))
        np.testing.assert_allclose(ds.features, [[0.1, 0.2], [0.3, 0.4]])
        np.testing.assert_allclose(ds.labels, [[1, 0], [0, 1]])

    def test_openpyxl_oracle_if_available(self, tmp_path):
        """If any real xlsx producer exists in the env, cross-check."""
        openpyxl = pytest.importorskip("openpyxl")
        from deeplearning4j_tpu.data.excel import ExcelRecordReader

        wb = openpyxl.Workbook()
        ws = wb.active
        ws.append(["h1", "h2"])
        ws.append([1.5, "x"])
        p = tmp_path / "o.xlsx"
        wb.save(p)
        assert list(ExcelRecordReader(p, skip_rows=1)) == [[1.5, "x"]]

    def test_shared_strings_path(self, tmp_path):
        """Hand-built xlsx with sharedStrings (what Excel itself writes),
        independent of our write_xlsx (which uses inline strings)."""
        import zipfile

        p = tmp_path / "ss.xlsx"
        ns = "http://schemas.openxmlformats.org/spreadsheetml/2006/main"
        with zipfile.ZipFile(p, "w") as zf:
            zf.writestr("[Content_Types].xml",
                '<?xml version="1.0"?><Types xmlns="http://schemas.'
                'openxmlformats.org/package/2006/content-types">'
                '<Default Extension="rels" ContentType="application/vnd.'
                'openxmlformats-package.relationships+xml"/>'
                '<Default Extension="xml" ContentType="application/xml"/>'
                '</Types>')
            zf.writestr("_rels/.rels",
                '<?xml version="1.0"?><Relationships xmlns="http://schemas.'
                'openxmlformats.org/package/2006/relationships">'
                '<Relationship Id="rId1" Type="http://schemas.openxmlformats'
                '.org/officeDocument/2006/relationships/officeDocument" '
                'Target="xl/workbook.xml"/></Relationships>')
            zf.writestr("xl/workbook.xml",
                f'<?xml version="1.0"?><workbook xmlns="{ns}" xmlns:r='
                '"http://schemas.openxmlformats.org/officeDocument/2006/'
                'relationships"><sheets>'
                '<sheet name="S" sheetId="1" r:id="rId1"/></sheets>'
                '</workbook>')
            zf.writestr("xl/_rels/workbook.xml.rels",
                '<?xml version="1.0"?><Relationships xmlns="http://schemas.'
                'openxmlformats.org/package/2006/relationships">'
                '<Relationship Id="rId1" Type="http://schemas.'
                'openxmlformats.org/officeDocument/2006/relationships/'
                'worksheet" Target="worksheets/sheet1.xml"/>'
                '</Relationships>')
            zf.writestr("xl/sharedStrings.xml",
                f'<?xml version="1.0"?><sst xmlns="{ns}" count="2" '
                'uniqueCount="2"><si><t>hello</t></si>'
                '<si><r><t>wor</t></r><r><t>ld</t></r></si></sst>')
            zf.writestr("xl/worksheets/sheet1.xml",
                f'<?xml version="1.0"?><worksheet xmlns="{ns}"><sheetData>'
                '<row r="1"><c r="A1" t="s"><v>0</v></c>'
                '<c r="B1" t="s"><v>1</v></c>'
                '<c r="C1"><v>2.5</v></c></row></sheetData></worksheet>')
        from deeplearning4j_tpu.data.excel import ExcelRecordReader

        assert list(ExcelRecordReader(p)) == [["hello", "world", 2.5]]

    def test_error_cells_and_missing_refs(self, tmp_path):
        """t='e' error cells -> None; cells without r= advance positionally."""
        import zipfile

        from deeplearning4j_tpu.data.excel import ExcelRecordReader, write_xlsx

        p = tmp_path / "e.xlsx"
        write_xlsx(p, [[1.0, 2.0]])
        # rewrite the sheet with an error cell and r-less cells
        ns = "http://schemas.openxmlformats.org/spreadsheetml/2006/main"
        import shutil
        with zipfile.ZipFile(p) as zf:
            names = {n: zf.read(n) for n in zf.namelist()}
        names["xl/worksheets/sheet1.xml"] = (
            f'<?xml version="1.0"?><worksheet xmlns="{ns}"><sheetData>'
            '<row r="1"><c><v>7</v></c><c t="e"><v>#DIV/0!</v></c>'
            '<c><v>9</v></c></row></sheetData></worksheet>').encode()
        with zipfile.ZipFile(p, "w") as zf:
            for n, data in names.items():
                zf.writestr(n, data)
        assert list(ExcelRecordReader(p)) == [[7.0, None, 9.0]]

    def test_ragged_trailing_blanks_rectangularized(self, tmp_path):
        from deeplearning4j_tpu.data.excel import ExcelRecordReader, write_xlsx

        p = tmp_path / "r.xlsx"
        write_xlsx(p, [[1.0, 2.0, 3.0], [4.0, None, None], [5.0, 6.0, None]])
        recs = list(ExcelRecordReader(p))
        assert all(len(r) == 3 for r in recs)
        assert recs[1] == [4.0, None, None]
