"""C++ batch engine vs the Python loader (skipped when no toolchain)."""

import os

import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.data import native_loader as nl
from pytorch_distributed_training_example_tpu.data.sampler import ShardedSampler

pytestmark = pytest.mark.skipif(not nl.available(),
                                reason="native engine unavailable (no g++)")


def test_gather_matches_numpy():
    data = np.random.RandomState(0).randint(0, 1000, (50, 16)).astype(np.int32)
    eng = nl.NativeBatchEngine.gather(data)
    idx = np.array([5, 0, 49, 17, 17])
    out = np.empty((5, 16), np.int32)
    eng.submit(0, idx, out)
    eng.wait(0)
    np.testing.assert_array_equal(out, data[idx])
    eng.close()


def test_image_normalize_matches_numpy():
    imgs = np.random.RandomState(1).randint(0, 256, (12, 8, 8, 3), np.uint8)
    mean, std = [0.4, 0.5, 0.6], [0.2, 0.3, 0.25]
    eng = nl.NativeBatchEngine.image(imgs, mean, std, augment=False)
    out = np.empty((12, 8, 8, 3), np.float32)
    eng.submit(0, np.arange(12), out)
    eng.wait(0)
    ref = (imgs.astype(np.float32) / 255.0 - np.asarray(mean, np.float32)) \
        / np.asarray(std, np.float32)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    eng.close()


def test_augment_deterministic_per_seed():
    imgs = np.random.RandomState(2).randint(0, 256, (6, 8, 8, 3), np.uint8)
    eng = nl.NativeBatchEngine.image(imgs, [0.5] * 3, [0.25] * 3, augment=True)
    a = np.empty((6, 8, 8, 3), np.float32)
    b = np.empty_like(a)
    c = np.empty_like(a)
    eng.submit(0, np.arange(6), a, seed=7)
    eng.submit(1, np.arange(6), b, seed=7)
    eng.submit(2, np.arange(6), c, seed=8)
    for i in range(3):
        eng.wait(i)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    eng.close()


def test_augment_invariant_to_chunking():
    """Per-sample RNG is keyed on the DATASET index, so splitting a batch
    across jobs (different --workers / chunk sizes) must not change the
    augmentation (ADVICE r2: chunk-relative seeding was not reproducible)."""
    imgs = np.random.RandomState(5).randint(0, 256, (6, 8, 8, 3), np.uint8)
    eng = nl.NativeBatchEngine.image(imgs, [0.5] * 3, [0.25] * 3, augment=True)
    whole = np.empty((6, 8, 8, 3), np.float32)
    split = np.empty_like(whole)
    eng.submit(0, np.arange(6), whole, seed=7)
    eng.submit(1, np.arange(3), split[:3], seed=7)        # chunk 1
    eng.submit(2, np.arange(3, 6), split[3:], seed=7)     # chunk 2
    for i in range(3):
        eng.wait(i)
    np.testing.assert_array_equal(whole, split)
    # reordered indices still get their own per-index stream
    perm = np.array([3, 1, 5, 0, 4, 2])
    reord = np.empty_like(whole)
    eng.submit(3, perm, reord, seed=7)
    eng.wait(3)
    np.testing.assert_array_equal(reord, whole[perm])
    eng.close()


def test_native_dataloader_iterates():
    imgs = np.random.RandomState(3).randint(0, 256, (40, 8, 8, 3), np.uint8)
    labels = np.arange(40) % 10
    sampler = ShardedSampler(40, 2, 0, shuffle=True, seed=0, drop_last=True)
    dl = nl.NativeDataLoader(imgs, labels, sampler, batch_size=4,
                             mean=[0.5] * 3, std=[0.25] * 3, augment=False)
    batches = list(dl)
    assert len(batches) == len(dl) == 5
    assert batches[0]["image"].shape == (4, 8, 8, 3)
    assert batches[0]["image"].dtype == np.float32
    # second epoch reshuffles
    dl.set_epoch(1)
    batches2 = list(dl)
    assert not np.array_equal(batches[0]["label"], batches2[0]["label"])
    # and the contents match the python gather for the same sampler order
    sampler2 = ShardedSampler(40, 2, 0, shuffle=True, seed=0, drop_last=True)
    sampler2.set_epoch(1)
    idx = sampler2.local_indices()[:4]
    ref = (imgs[idx].astype(np.float32) / 255.0 - 0.5) / 0.25
    np.testing.assert_allclose(batches2[0]["image"], ref, atol=1e-5)


def test_native_dataloader_start_batch_matches_suffix():
    """start_batch (mid-epoch resume) on the native loader yields exactly
    the suffix of the full epoch stream — same contract as DataLoader."""
    imgs = np.random.RandomState(5).randint(0, 256, (48, 8, 8, 3), np.uint8)
    labels = np.arange(48) % 10
    mk = lambda: nl.NativeDataLoader(
        imgs, labels, ShardedSampler(48, 1, 0, shuffle=True, seed=2,
                                     drop_last=True),
        batch_size=4, mean=[0.5] * 3, std=[0.25] * 3, augment=False)
    full = list(mk())
    dl = mk()
    dl.start_batch = 7
    tail = list(dl)
    assert len(tail) == len(full) - 7
    for a, b in zip(full[7:], tail):
        np.testing.assert_array_equal(a["label"], b["label"])
        np.testing.assert_allclose(a["image"], b["image"])


def test_native_dataloader_early_abandon_drains():
    """Breaking out of iteration must not leave C++ jobs writing into freed bufs."""
    imgs = np.random.RandomState(4).randint(0, 256, (64, 8, 8, 3), np.uint8)
    labels = np.arange(64) % 10
    sampler = ShardedSampler(64, 1, 0, shuffle=False, drop_last=True)
    dl = nl.NativeDataLoader(imgs, labels, sampler, batch_size=4,
                             mean=[0.5] * 3, std=[0.25] * 3, augment=False,
                             prefetch=4)
    for ep in range(3):  # repeated early abandonment across epochs
        dl.set_epoch(ep)
        it = iter(dl)
        next(it)
        next(it)
        it.close()
    # full pass afterwards still correct
    first = next(iter(dl))
    idx = dl.sampler.local_indices()[:4]
    ref = (imgs[idx].astype(np.float32) / 255.0 - 0.5) / 0.25
    np.testing.assert_allclose(first["image"], ref, atol=1e-5)


def test_token_loader_matches_python_bitforbit(tmp_path):
    """Native window-gather over a token file == TokenFileDataset through the
    Python loader, same sampler order."""
    from pytorch_distributed_training_example_tpu.data.datasets import (
        TokenFileDataset)
    from pytorch_distributed_training_example_tpu.data.loader import (
        DataLoader, build_image_loader)

    rng = np.random.RandomState(5)
    toks = rng.randint(0, 50000, 4097).astype(np.uint16)
    path = tmp_path / "tokens.bin"
    toks.tofile(path)
    ds = TokenFileDataset(str(path), seq_len=128)
    assert len(ds) == 32

    sampler = ShardedSampler(len(ds), shuffle=True, seed=2, drop_last=True)
    native = build_image_loader(ds, sampler, batch_size=4, workers=2)
    assert isinstance(native, nl.NativeTokenDataLoader)
    sampler_py = ShardedSampler(len(ds), shuffle=True, seed=2, drop_last=True)
    python = DataLoader(ds, 4, sampler_py, num_workers=0)

    native.set_epoch(1)
    python.set_epoch(1)
    nb, pb = list(native), list(python)
    assert len(nb) == len(pb) == 8
    for a, b in zip(nb, pb):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["targets"], b["targets"])
        assert a["tokens"].dtype == np.int32


def test_native_dataloader_rejects_drop_last_false():
    imgs = np.zeros((8, 4, 4, 3), np.uint8)
    with pytest.raises(ValueError, match="drop_last"):
        nl.NativeDataLoader(imgs, np.zeros(8), ShardedSampler(8), 4,
                            [0.5] * 3, [0.25] * 3, False, drop_last=False)


def test_failed_build_never_loads_a_stale_library(monkeypatch, caplog):
    """A library this process did not just build (or make did not find up
    to date) is never loaded: the .so is untracked, so what lies there may
    have an old C ABI. A failed make means the Python loader, loudly."""
    import logging
    import subprocess

    assert os.path.exists(nl._LIB_PATH)  # available() above built it

    def no_make(*a, **k):
        raise subprocess.CalledProcessError(2, "make", stderr=b"g++: not found")

    monkeypatch.setattr(nl, "_lib", None)
    monkeypatch.setattr(nl.subprocess, "run", no_make)
    with caplog.at_level(logging.ERROR, logger=nl.log.name):
        assert nl._load() is None
        assert not nl.available()
    assert "NOT built" in caplog.text and "g++: not found" in caplog.text
