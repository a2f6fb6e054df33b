"""Autotune-table machinery: persisted-table roundtrip, runtime entries,
tile clamping (fit_block) and tail-block tile picking (pick_tile)."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import dispatch, ops


class TestPickTile:
    """pick_tile chooses the row tile from the TRUE dim before padding —
    the tail-block fix (a 1-row decode matmul must not pad to 128)."""

    def test_decode_row(self):
        assert dispatch.pick_tile(1, 128) == 8       # one f32 sublane

    def test_non_multiple_prefill(self):
        assert dispatch.pick_tile(100, 128) == 104   # next multiple of 8

    def test_large_dim_capped_by_request(self):
        assert dispatch.pick_tile(256, 128) == 128

    def test_request_below_multiple(self):
        assert dispatch.pick_tile(64, 4) == 8

    def test_exact(self):
        assert dispatch.pick_tile(128, 128) == 128


class TestFitBlock:
    def test_clamps_to_dim(self):
        # a table entry tuned for a big bucket cannot force a small
        # problem to pad up to the entry's tile
        assert dispatch.fit_block(8, 512) == 8

    def test_single_row(self):
        assert dispatch.fit_block(1, 128) == 1

    def test_divisor_with_multiple(self):
        # largest tile <= 1024 dividing 1536 that is a multiple of 256
        assert dispatch.fit_block(1536, 1024, 256) == 768

    def test_exact_fit(self):
        assert dispatch.fit_block(2048, 512) == 512


class TestTableRoundtrip:
    ENTRY = {"op": "int8_matmul", "backend": "pallas-interpret",
             "shape": [256, 512], "dtype": "float32",
             "blocks": {"bm": 64, "bn": 512, "bk": 256},
             "source": "measured"}

    def _with_table(self, tmp_path, monkeypatch, entries):
        p = str(tmp_path / "table.json")
        dispatch.save_table_entries(entries, p)
        monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", p)
        dispatch.reload_table()
        return p

    def test_persist_load_dispatch(self, tmp_path, monkeypatch):
        self._with_table(tmp_path, monkeypatch, [self.ENTRY])
        # the query shape buckets to the stored (256, 512)
        got = dispatch.tuned_blocks("int8_matmul", (200, 500), "float32",
                                    backend="pallas-interpret")
        assert got == {"bm": 64, "bn": 512, "bk": 256}

    def test_any_dtype_fallback(self, tmp_path, monkeypatch):
        e = dict(self.ENTRY, dtype="")
        self._with_table(tmp_path, monkeypatch, [e])
        got = dispatch.tuned_blocks("int8_matmul", (256, 512), "bfloat16",
                                    backend="pallas-interpret")
        assert got == {"bm": 64, "bn": 512, "bk": 256}

    def test_miss_falls_back_to_defaults(self, tmp_path, monkeypatch):
        self._with_table(tmp_path, monkeypatch, [self.ENTRY])
        got = dispatch.tuned_blocks("int8_matmul", (4096, 4096), "float32",
                                    backend="pallas-interpret")
        assert got == dispatch._DEFAULT_BLOCKS["int8_matmul"]

    def test_runtime_registration_wins(self, tmp_path, monkeypatch):
        self._with_table(tmp_path, monkeypatch, [self.ENTRY])
        dispatch.register_tuned("int8_matmul", "pallas-interpret",
                                (256, 512), {"bm": 8, "bn": 256, "bk": 128},
                                "float32")
        try:
            got = dispatch.tuned_blocks("int8_matmul", (256, 512),
                                        "float32",
                                        backend="pallas-interpret")
            assert got == {"bm": 8, "bn": 256, "bk": 128}
        finally:
            dispatch._RUNTIME_TABLE.clear()

    def test_save_dedups_last_wins(self, tmp_path, monkeypatch):
        e2 = dict(self.ENTRY, blocks={"bm": 128, "bn": 256, "bk": 512})
        p = self._with_table(tmp_path, monkeypatch, [self.ENTRY, e2])
        doc = json.load(open(p))
        assert doc["version"] == 1
        assert len(doc["entries"]) == 1
        assert doc["entries"][0]["blocks"] == e2["blocks"]

    def test_merge_keeps_seed_entries(self, tmp_path, monkeypatch):
        seed = dict(self.ENTRY, shape=[4096, 4096], source="seed")
        p = self._with_table(tmp_path, monkeypatch, [seed])
        merged = dispatch.load_table_entries(p) + [self.ENTRY]
        dispatch.save_table_entries(merged, p)
        doc = json.load(open(p))
        assert {tuple(e["shape"]) for e in doc["entries"]} == \
            {(4096, 4096), (256, 512)}

    def test_committed_table_loads(self):
        # the in-repo table parses and serves the seed entries
        entries = dispatch.load_table_entries(dispatch._TABLE_FILE)
        assert entries, "committed autotune_table.json is empty"
        assert all(e["source"] in ("seed", "measured") for e in entries)


class TestTrainingShapeEntries:
    """The committed table answers the training step's INT8 matmuls (the
    benchmark's InternLM2-1.8B cell: 8192 rows per update) with measured
    bf16 tiles, keyed by the bucketed (rows, K) the wrappers query."""

    @pytest.fixture(autouse=True)
    def _committed_table(self, monkeypatch):
        monkeypatch.delenv("REPRO_AUTOTUNE_TABLE", raising=False)
        dispatch.reload_table()

    @pytest.mark.parametrize("K,N", [(2048, 2048), (2048, 1024),
                                     (2048, 8192), (8192, 2048),
                                     (2048, 92553)])
    @pytest.mark.parametrize("op", ["int8_matmul", "int8_matmul_t"])
    def test_measured_entry(self, op, K, N):
        """Each training matmul finds its bucket's measured entry, and the
        wrapper's clamps leave it a tile that divides the padded problem:
        the head's 92,672 columns (181 x 512) take 512, never the whole
        width."""
        blocks, source = dispatch.tuned_lookup(
            op, (8192, K), "bfloat16", backend="pallas-tpu")
        assert source == "measured"
        entry = next(e for e in dispatch.load_table_entries(
            dispatch._TABLE_FILE) if e["op"] == op and e["shape"] == [8192, K])
        assert blocks == entry["blocks"]
        assert entry["us"] > 0 and entry["problem"][:2] == [8192, K]
        n_pad = -(-N // 256) * 256
        bn = dispatch.fit_block(n_pad, blocks["bn"], 256)
        assert n_pad % bn == 0 and bn <= blocks["bn"]
        assert bn == (512 if N == 92553 else min(blocks["bn"], n_pad))
        assert dispatch.fit_block(K, blocks["bk"], 128) == blocks["bk"]

    def test_other_dtype_and_shape_fall_back(self):
        """f32 activations and serving's decode rows do not reach them."""
        for shape, dtype in (((8192, 2048), "float32"),
                             ((8, 2048), "bfloat16")):
            assert dispatch.tuned_lookup(
                "int8_matmul", shape, dtype,
                backend="pallas-tpu")[1] == "default"

    def test_runtime_registration_wins_over_measured(self):
        """A sweep candidate registered at the key the wrapper queries is
        what the wrapper runs, over the committed measured entry."""
        cand = {"bm": 256, "bn": 512, "bk": 512}
        dispatch.register_tuned("int8_matmul", "pallas-tpu", (8192, 2048),
                                cand, "bfloat16")
        try:
            assert dispatch.tuned_lookup(
                "int8_matmul", (8192, 2048), "bfloat16",
                backend="pallas-tpu") == (cand, "runtime")
        finally:
            dispatch._RUNTIME_TABLE.clear()


class TestTunedBlocksReachKernel:
    def test_wrapper_honors_runtime_entry(self, monkeypatch):
        """A registered entry flows through the ops wrapper into a
        working (and correct) kernel launch at a non-tile-multiple
        shape."""
        M, K, N = 9, 96, 160
        x = jnp.asarray(np.random.default_rng(0).normal(size=(M, K)),
                        jnp.float32)
        w = jnp.asarray(np.random.default_rng(1).normal(size=(K, N)) * 0.1,
                        jnp.float32)
        qt = quant.quantize_blockwise(w, bits=8, symmetric=True)
        dispatch.register_tuned("int8_matmul", "pallas-interpret", (M, K),
                                {"bm": 64, "bn": 256, "bk": 32}, "float32")
        try:
            got = ops.int8_matmul(x, qt, backend="pallas-interpret")
        finally:
            dispatch._RUNTIME_TABLE.clear()
        want = x @ quant.dequantize(qt, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)
