"""Native data loader tests (C++ prefetch ring + numpy fallback)."""

import numpy as np
import pytest

from kubedl_tpu.data import TokenFileDataset, native_available
from kubedl_tpu.data.native import NativeTokenLoader, _NumpyTokenLoader


@pytest.fixture()
def token_file(tmp_path):
    toks = np.arange(10_000, dtype=np.int32) % 1000
    p = tmp_path / "tokens.bin"
    toks.tofile(p)
    return str(p), toks


def test_native_loader_builds_and_samples(token_file):
    path, toks = token_file
    if not native_available():
        pytest.skip("no g++ in this environment")
    ld = NativeTokenLoader(path, batch=4, seq=64, seed=7)
    try:
        assert ld.n_tokens == 10_000
        b = ld.next()
        assert b.shape == (4, 64) and b.dtype == np.int32
        # every row is a contiguous window of the source stream
        for row in b:
            start = int(row[0]) if row[0] == toks[row[0]] else None
            diffs = np.diff(row.astype(np.int64)) % 1000
            assert set(diffs.tolist()) <= {1, -999 % 1000}
        # deterministic: same seed -> same batches
        ld2 = NativeTokenLoader(path, batch=4, seq=64, seed=7)
        np.testing.assert_array_equal(ld2.next(), b)
        ld2.close()
    finally:
        ld.close()


def test_native_prefetch_many_batches(token_file):
    path, _ = token_file
    if not native_available():
        pytest.skip("no g++ in this environment")
    ld = NativeTokenLoader(path, batch=8, seq=128, prefetch=4)
    try:
        for _ in range(50):
            b = ld.next()
            assert b.shape == (8, 128)
            assert (b >= 0).all() and (b < 1000).all()
    finally:
        ld.close()


def test_numpy_fallback_same_contract(token_file):
    path, _ = token_file
    ld = _NumpyTokenLoader(path, batch=4, seq=64, seed=7)
    b = ld.next()
    assert b.shape == (4, 64) and b.dtype == np.int32
    diffs = np.diff(b.astype(np.int64), axis=1) % 1000
    assert set(np.unique(diffs).tolist()) <= {1}


def test_token_file_dataset_feeds_trainer(token_file, tmp_path):
    """End to end: a token FILE (not synthetic) through the trainer."""
    import jax

    from kubedl_tpu.api.topology import MeshSpec
    from kubedl_tpu.models import llama
    from kubedl_tpu.parallel.mesh import build_mesh
    from kubedl_tpu.training.trainer import TrainConfig, Trainer

    path, _ = token_file
    mesh = build_mesh(MeshSpec({"data": 2}), jax.devices()[:2])
    cfg = TrainConfig(model=llama.TINY, global_batch=4, seq_len=32, steps=2)
    trainer = Trainer(cfg, mesh)
    data = TokenFileDataset(path, 4, 32, seed=1)
    try:
        toks_iter = (np.clip(b, 0, llama.TINY.vocab_size - 1) for b in data)
        state, summary = trainer.fit(toks_iter)
        assert np.isfinite(summary["final_loss"])
    finally:
        data.close()


def test_bad_file_raises(tmp_path):
    small = tmp_path / "small.bin"
    np.arange(4, dtype=np.int32).tofile(small)
    with pytest.raises((FileNotFoundError, RuntimeError)):
        TokenFileDataset(str(small), 2, 64)


def test_native_loader_same_seed_same_sequence(token_file):
    """Two prefetch threads fill the ring, yet a seed must give one fixed
    SEQUENCE of batches (they enter the ring in the order their windows
    were drawn): a four-chip run is compared step by step with a one-chip
    run on the same batches (chip_smoke.py --multichip)."""
    path, _ = token_file
    if not native_available():
        pytest.skip("no g++ in this environment")
    runs = []
    for _ in range(3):
        ld = NativeTokenLoader(path, batch=8, seq=128, seed=11, prefetch=4)
        try:
            runs.append(np.stack([ld.next() for _ in range(64)]))
        finally:
            ld.close()
    np.testing.assert_array_equal(runs[0], runs[1])
    np.testing.assert_array_equal(runs[0], runs[2])
    assert len({b.tobytes() for b in runs[0]}) > 1  # not one batch repeated
