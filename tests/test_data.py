import numpy as np

from pytorch_distributed_training_example_tpu.data import prefetch
from pytorch_distributed_training_example_tpu.data.datasets import (
    SyntheticImageDataset, SyntheticTokenDataset, build_dataset)
from pytorch_distributed_training_example_tpu.data.loader import DataLoader
from pytorch_distributed_training_example_tpu.data.sampler import ShardedSampler
from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib


def test_loader_shapes_and_count():
    ds = SyntheticImageDataset(100, 16, 10)
    dl = DataLoader(ds, batch_size=8, drop_last=True)
    batches = list(dl)
    assert len(batches) == len(dl) == 12
    assert batches[0]["image"].shape == (8, 16, 16, 3)
    assert batches[0]["label"].shape == (8,)


def test_threaded_loader_matches_serial():
    ds = SyntheticImageDataset(64, 8, 10)
    sampler = ShardedSampler(64, 2, 1, shuffle=True, seed=1)
    serial = list(DataLoader(ds, 4, sampler, num_workers=0))
    threaded = list(DataLoader(ds, 4, sampler, num_workers=3))
    assert len(serial) == len(threaded)
    for a, b in zip(serial, threaded):
        np.testing.assert_array_equal(a["image"], b["image"])
        np.testing.assert_array_equal(a["label"], b["label"])


def test_token_dataset_targets_shifted():
    ds = SyntheticTokenDataset(4, seq_len=16, vocab_size=100)
    s = ds[0]
    assert s["tokens"].shape == (16,)
    np.testing.assert_array_equal(s["tokens"][1:], s["targets"][:-1])


def test_device_prefetch_shards_batch(devices):
    mesh = mesh_lib.build_mesh({"data": 8})
    ds = SyntheticImageDataset(64, 8, 10)
    dl = DataLoader(ds, batch_size=16)
    out = list(prefetch.device_prefetch(dl, mesh_lib.batch_sharding(mesh)))
    assert len(out) == 4
    x = out[0]["image"]
    assert x.shape == (16, 8, 8, 3)
    assert len(x.addressable_shards) == 8


def test_build_dataset_synthetic_fallback():
    ds = build_dataset("cifar10", None, train=True)
    assert ds[0]["image"].shape == (32, 32, 3)
    lm = build_dataset("lm", None, train=True, seq_len=64)
    assert lm[0]["tokens"].shape == (64,)


def test_loader_start_batch_skips_exact_prefix():
    """Mid-epoch resume contract: start_batch=k yields exactly the suffix
    of the epoch's deterministic batch stream, bit-for-bit, in both the
    serial and threaded paths."""
    ds = SyntheticImageDataset(96, 8, 10)
    sampler = ShardedSampler(96, 1, 0, shuffle=True, seed=3)
    full = list(DataLoader(ds, 8, sampler, num_workers=0))
    for workers in (0, 2):
        dl = DataLoader(ds, 8, ShardedSampler(96, 1, 0, shuffle=True, seed=3),
                        num_workers=workers)
        dl.start_batch = 5
        tail = list(dl)
        assert len(tail) == len(full) - 5
        for a, b in zip(full[5:], tail):
            np.testing.assert_array_equal(a["image"], b["image"])
            np.testing.assert_array_equal(a["label"], b["label"])


def test_loader_index_log_records_absolute_batches(tmp_path, monkeypatch):
    """PDTX_INDEX_LOG writes one line per yielded batch with the ABSOLUTE
    batch number, so resumed runs can be compared against the full epoch
    stream for the no-replay/no-skip assertion."""
    import json

    from pytorch_distributed_training_example_tpu.data import loader as loader_lib

    log = tmp_path / "idx.jsonl"
    monkeypatch.setenv(loader_lib.INDEX_LOG_ENV, str(log))
    ds = SyntheticImageDataset(64, 8, 10)
    sampler = ShardedSampler(64, 1, 0, shuffle=True, seed=7)
    dl = DataLoader(ds, 8, sampler)
    dl.set_epoch(2)
    dl.start_batch = 3
    list(dl)
    rows = [json.loads(l) for l in log.read_text().splitlines()]
    assert [r["batch"] for r in rows] == [3, 4, 5, 6, 7]
    assert all(r["epoch"] == 2 for r in rows)
    want = sampler.local_indices()[3 * 8:]
    got = [i for r in rows for i in r["indices"]]
    np.testing.assert_array_equal(got, want)


def test_pad_batch_mask():
    b = {"image": np.ones((5, 4, 4, 3), np.float32), "label": np.arange(5)}
    out = prefetch.pad_batch(b, 8)
    assert out["image"].shape == (8, 4, 4, 3)
    np.testing.assert_array_equal(out["mask"], [1, 1, 1, 1, 1, 0, 0, 0])
    full = prefetch.pad_batch({"label": np.arange(8)}, 8)
    np.testing.assert_array_equal(full["mask"], np.ones(8))


def test_dp_shard_coordinate_mapping():
    """Loader sharding keys on the dp COORDINATE, not the process index:
    hosts holding only seq/pp/ep/tp shards of one replica read the same
    sample stream (ISSUE 20 satellite: nproc % dp == 0 generalization)."""
    from pytorch_distributed_training_example_tpu.data import loader as loader_lib

    # Plain multi-host data parallel: each host its own slice.
    assert loader_lib.dp_shard(2, 4, 1) == (2, 1)
    assert loader_lib.dp_shard(4, 4, 3) == (4, 3)
    # dp1 x seq2 gang: both ranks -> coordinate 0, identical rows.
    assert loader_lib.dp_shard(2, 1, 0) == (1, 0)
    assert loader_lib.dp_shard(2, 1, 1) == (1, 0)
    # dp2 x (seq or pp)2 over 4 processes: contiguous pairs share a stream.
    assert [loader_lib.dp_shard(4, 2, p)[1] for p in range(4)] == [0, 0, 1, 1]
    # Indivisible gangs fail loudly.
    import pytest
    with pytest.raises(ValueError, match="multiple of"):
        loader_lib.dp_shard(3, 2, 0)


def test_threaded_loader_cannot_starve_the_awaited_batch():
    """Look-ahead is granted in batch order: with an unordered permit pool,
    workers of later batches could take every permit while the worker of
    the batch the consumer waits for starved — the trainer hung at its
    first batches (found rehearsing chip_smoke.py). Many fast batches make
    the old race near-certain; the run must finish, in order."""
    import sys
    import threading

    ds = SyntheticTokenDataset(num_examples=4096, seq_len=8, vocab_size=64)
    ldr = DataLoader(ds, 2, num_workers=16)  # more workers than cores
    got = []
    t = threading.Thread(target=lambda: got.extend(b["tokens"] for b in ldr),
                         daemon=True)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # switch threads often: provoke the race
    try:
        t.start()
        t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not t.is_alive(), "threaded DataLoader deadlocked"
    assert len(got) == 2048
    serial = [b["tokens"] for b in DataLoader(ds, 2, num_workers=0)]
    assert all(np.array_equal(a, b) for a, b in zip(got, serial))
