"""Guard-rail and observability utilities (SURVEY.md §5): watchdog, timeout
blocking, metric logging — small pieces the trainer leans on every step."""

import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.utils import (
    logging as log_lib, metrics as metrics_lib, watchdog as wd)


class _Capture(logging.Handler):
    """Handler attached straight to the 'pdtx' logger: trainer tests run
    setup_logging() which sets propagate=False, so caplog's root-logger
    handler misses watchdog records inside the full suite."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records = []

    def emit(self, record):
        self.records.append(record)


def test_watchdog_fires_and_recovers():
    logger = logging.getLogger("pdtx")
    cap = _Capture()
    logger.addHandler(cap)
    old_level = logger.level
    logger.setLevel(logging.ERROR)
    try:
        # Generous windows + deadline polling: the suite runs on a
        # contended single-core box where thread scheduling can lag.
        w = wd.Watchdog(timeout_s=0.5).start()
        try:
            deadline = time.monotonic() + 15.0
            while (not any("watchdog" in r.getMessage() for r in cap.records)
                   and time.monotonic() < deadline):
                time.sleep(0.05)  # no beats -> must fire eventually
            assert any("watchdog" in r.getMessage() for r in cap.records)
        finally:
            w.stop()

        # Heartbeats keep it silent over a window long enough for the idle
        # check (every timeout/4 = 0.5s) to run at least once; the 2s
        # timeout tolerates scheduler stalls without re-flaking.
        w2 = wd.Watchdog(timeout_s=2.0).start()
        try:
            cap.records.clear()
            deadline = time.monotonic() + 1.2
            while time.monotonic() < deadline:
                w2.beat()
                time.sleep(0.02)
            assert not cap.records
        finally:
            w2.stop()
    finally:
        logger.removeHandler(cap)
        logger.setLevel(old_level)


def test_block_with_timeout_passes_and_raises():
    x = jnp.ones((4,)) * 2
    wd.block_until_ready_with_timeout(x, timeout_s=30)

    class Never:
        # The hung-dispatch contract is polled via is_ready() (r9: the old
        # helper-thread-in-block_until_ready version leaked the thread).
        def is_ready(self):
            return False

        def block_until_ready(self):
            time.sleep(60)

    with pytest.raises(TimeoutError, match="not ready"):
        wd.block_until_ready_with_timeout(Never(), timeout_s=0.3)


def test_metric_logger_jsonl_roundtrip(tmp_path):
    path = tmp_path / "m" / "metrics.jsonl"
    ml = log_lib.MetricLogger(str(path))
    ml.write(kind="train", step=1, loss=2.5)
    ml.write(kind="eval", loss=np.float32(1.25))  # numpy scalars serialize
    ml.close()
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert rows[0]["kind"] == "train" and rows[0]["loss"] == 2.5
    assert rows[1]["loss"] == 1.25 and "time" in rows[1]


def test_average_meter_and_throughput():
    m = log_lib.AverageMeter("loss")
    m.update(2.0)
    m.update(4.0, n=3)
    assert m.avg == pytest.approx(3.5)
    t = log_lib.Throughput(warmup_steps=1)
    t.update(10)          # warmup step sets t0
    time.sleep(0.05)
    t.update(10)
    assert 0 < t.rate < 10_000


def test_mfu_accounting():
    # 1000 img/s at 4.09 GFLOP fwd => 3x fwd+bwd = 12.27 TF/s achieved.
    class FakeDev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    mfu = metrics_lib.mfu(1000.0, 4.09e9, device=FakeDev())
    assert mfu == pytest.approx(3 * 4.09e12 / 197e12)
    assert metrics_lib.peak_hbm_gbps(FakeDev()) == 819.0


def test_peaks_unknown_tpu_raises_and_cpu_is_not_measured():
    """No nominal default: an unknown TPU kind is an error, and on platform
    cpu MFU / roofline peaks are "not measured" (None), never 1e12."""
    class Unknown:
        platform = "tpu"
        device_kind = "TPU v99 mega"

    for fn in (metrics_lib.peak_flops_per_chip, metrics_lib.peak_hbm_gbps):
        with pytest.raises(ValueError, match="v99 mega"):
            fn(Unknown())
    with pytest.raises(ValueError, match="v99 mega"):
        metrics_lib.mfu(1000.0, 4.09e9, device=Unknown())
    cpu = jax.devices()[0]
    assert cpu.platform == "cpu"
    assert metrics_lib.peak_flops_per_chip(cpu) is None
    assert metrics_lib.peak_hbm_gbps(cpu) is None
    assert metrics_lib.mfu(1000.0, 4.09e9) is None


def test_metric_logger_tensorboard_export(tmp_path):
    """SURVEY.md §5 optional TensorBoard scalars: numeric metrics land as
    event-file scalars tagged kind/name at the given step; non-numerics
    are skipped; JSONL keeps working alongside."""
    pytest.importorskip("tensorboard")
    from pytorch_distributed_training_example_tpu.utils.logging import MetricLogger

    tb = tmp_path / "tb"
    ml = MetricLogger(jsonl_path=str(tmp_path / "m.jsonl"),
                      tensorboard_dir=str(tb))
    ml.write(kind="train", step=3, loss=1.5, acc_top1=0.25, note="skip-me")
    ml.write(kind="eval", epoch=1, loss=2.0)
    ml.close()

    from tensorboard.backend.event_processing.event_accumulator import (
        EventAccumulator)

    acc = EventAccumulator(str(tb))
    acc.Reload()
    tags = set(acc.Tags()["scalars"])
    assert {"train/loss", "train/acc_top1", "eval/loss"} <= tags, tags
    ev = acc.Scalars("train/loss")[0]
    assert ev.step == 3 and abs(ev.value - 1.5) < 1e-6
    assert "train/note" not in tags
    assert (tmp_path / "m.jsonl").read_text().count("\n") == 2


def test_metric_logger_tensorboard_step_axes(tmp_path):
    """Eval rows (epoch-keyed) land on the global-step axis when the
    trainer provides steps_per_epoch, so train/eval scalars are
    comparable; per-kind counters never move backwards (ADVICE r4)."""
    pytest.importorskip("tensorboard")
    from pytorch_distributed_training_example_tpu.utils.logging import MetricLogger

    tb = tmp_path / "tb"
    ml = MetricLogger(tensorboard_dir=str(tb))
    ml.steps_per_epoch = 100
    ml.write(kind="train", epoch=0, step=99, loss=1.0)
    ml.write(kind="eval", epoch=0, loss=2.0)    # -> global step 99
    ml.write(kind="train", epoch=1, step=199, loss=0.5)
    ml.write(kind="eval", epoch=1, loss=1.5)    # -> global step 199
    ml.close()

    from tensorboard.backend.event_processing.event_accumulator import (
        EventAccumulator)

    acc = EventAccumulator(str(tb))
    acc.Reload()
    assert [e.step for e in acc.Scalars("eval/loss")] == [99, 199]
    assert [e.step for e in acc.Scalars("train/loss")] == [99, 199]


def test_lr_schedules_reference_recipes():
    """Schedule parity: 'step' reproduces the reference ImageNet StepLR
    (lr * gamma^(epoch // 30)); cosine + warmup keeps its r4 shape
    (linear to peak at warmup end, cosine to 0 at the horizon);
    'constant' is flat after warmup."""
    from pytorch_distributed_training_example_tpu.core import optim
    from pytorch_distributed_training_example_tpu.utils.config import Config

    spe = 100
    step = optim.build_schedule(
        Config(lr=0.1, warmup_epochs=0.0, lr_schedule="step",
               lr_step_epochs=30, lr_gamma=0.1, epochs=90), spe)
    assert float(step(0)) == pytest.approx(0.1)
    assert float(step(29 * spe + 99)) == pytest.approx(0.1)
    assert float(step(30 * spe)) == pytest.approx(0.01)
    assert float(step(60 * spe)) == pytest.approx(0.001)

    # ...and the decay epochs stay on the GLOBAL grid under warmup: the
    # reference recipe decays at epochs 30/60 regardless of warmup.
    stepw = optim.build_schedule(
        Config(lr=0.1, warmup_epochs=5.0, lr_schedule="step",
               lr_step_epochs=30, lr_gamma=0.1, epochs=90), spe)
    assert float(stepw(5 * spe // 2)) == pytest.approx(0.05)  # mid-warmup
    assert float(stepw(29 * spe + 99)) == pytest.approx(0.1)
    assert float(stepw(30 * spe)) == pytest.approx(0.01)
    assert float(stepw(60 * spe)) == pytest.approx(0.001)

    cos = optim.build_schedule(
        Config(lr=0.4, warmup_epochs=1.0, lr_schedule="cosine", epochs=10),
        spe)
    assert float(cos(0)) == pytest.approx(0.0)
    assert float(cos(spe)) == pytest.approx(0.4)       # peak at warmup end
    assert float(cos(10 * spe)) == pytest.approx(0.0, abs=1e-6)
    # halfway through the cosine phase = half the peak
    assert float(cos(spe + (9 * spe) // 2)) == pytest.approx(0.2, rel=0.01)

    const = optim.build_schedule(
        Config(lr=0.05, warmup_epochs=0.0, lr_schedule="constant",
               epochs=5), spe)
    assert float(const(0)) == float(const(499)) == pytest.approx(0.05)

    with pytest.raises(ValueError, match="lr_schedule"):
        optim.build_schedule(Config(lr_schedule="nope"), spe)


# ---- no fallback that hides the device (entry points, off-chip) ----------

import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_off_chip(*argv):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_off_chip():
    """chip_smoke.py has no CPU mode: platform != tpu -> non-zero exit and
    ``"ok": false`` in the contract's last line, before any other work."""
    res = _run_off_chip("chip_smoke.py")
    assert res.returncode != 0
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "gpt2_train" not in res.stdout  # no phase ran


def test_launcher_import_initialises_no_backend():
    """launch.py imports the package (whose __init__ imports jax), but must
    never initialise a backend: on a TPU host that would take the chip from
    the one child that needs it."""
    res = _run_off_chip(
        "-c", "import launch; from jax._src import xla_bridge as xb; "
        "assert not xb.backends_are_initialized(), 'backend initialised'; "
        "print('clean')")
    assert res.returncode == 0, res.stderr
    assert "clean" in res.stdout
