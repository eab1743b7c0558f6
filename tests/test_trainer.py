"""Trainer-level integration (SURVEY.md §4.4): epochs, eval, checkpoint,
resume — end-to-end through the same object main.py drives."""

import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.core.trainer import Trainer
from pytorch_distributed_training_example_tpu.utils.config import Config


def _cfg(tmp_path, **kw):
    base = dict(model="resnet_micro", dataset="cifar10", num_classes=10,
                image_size=32, epochs=2, global_batch_size=32, lr=0.05,
                warmup_epochs=0.0, precision="fp32", workers=0,
                steps_per_epoch=3, log_every=3,
                checkpoint_dir=str(tmp_path / "ck"))
    base.update(kw)
    return Config(**base)


@pytest.mark.slow
def test_trainer_trains_evals_checkpoints_resumes(tmp_path, devices):
    t = Trainer(_cfg(tmp_path))
    t.train()
    import os

    cks = [d for d in os.listdir(tmp_path / "ck") if d.startswith("step_")]
    assert len(cks) >= 1
    metrics_file = tmp_path / "ck" / "metrics.jsonl"
    assert metrics_file.exists() and metrics_file.read_text().strip()

    # resume continues from the stored epoch
    t2 = Trainer(_cfg(tmp_path, epochs=3, resume="auto"))
    assert t2.start_epoch == 2
    assert int(np.asarray(t2.state.step)) == 6  # 2 epochs x 3 steps


@pytest.mark.slow
def test_trainer_loss_decreases_over_epochs(tmp_path, devices):
    cfg = _cfg(tmp_path, epochs=4, steps_per_epoch=4, checkpoint_dir=None,
               lr=0.08, seed=1)
    t = Trainer(cfg)
    losses = []
    for epoch in range(cfg.epochs):
        t.train_epoch(epoch)
    # eval on the train distribution: synthetic labels are deterministic per
    # index, so the model can fit them — loss must end below chance level
    final = t.evaluate(cfg.epochs - 1)
    assert final["loss"] < 2.31  # below uniform-random CE = ln(10)


# -- one path from a Config to the step program -------------------------------


def _lm_cfg(model, **kw):
    base = dict(model=model, dataset="lm", seq_len=32, epochs=1,
                global_batch_size=16, lr=1e-3, warmup_epochs=0.0,
                optimizer="adamw", weight_decay=0.1, grad_clip=1.0,
                precision="fp32", strategy="fsdp", mesh_data=2, mesh_fsdp=4,
                workers=0, steps_per_epoch=2, log_every=100)
    base.update(kw)
    return Config(**base)


def test_trainer_runs_the_builders_program(devices):
    """What the ``Trainer`` runs is what ``build_step_program`` returns: the
    text its own ``train_step`` lowers to on its state and a batch is the
    text a tool gets from the record with shapes in the state's place."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib, trainer as trainer_lib)

    cfg = _lm_cfg("gpt2_tiny")
    t = Trainer(cfg)
    tokens = jax.ShapeDtypeStruct((cfg.global_batch_size, cfg.seq_len),
                                  jnp.int32, sharding=t.batch_sharding)
    batch = {"tokens": tokens, "targets": tokens}
    program = trainer_lib.build_step_program(
        cfg, t.mesh, t.steps_per_epoch, trainer_lib.build_model(cfg))
    with mesh_lib.use_mesh(t.mesh):
        ran = t.train_step.lower(t.state, batch).as_text()
        built = program.train_step.lower(program.abstract_state(),
                                         batch).as_text()
    assert "sharding" in ran and ran == built


@pytest.mark.parametrize("model,optimizer,strategy,b2", [
    ("gpt2_tiny", "adamw", "fsdp", 0.95),
    ("granite_hybrid_tiny", "adamw", "fsdp", 0.95),
    ("vit_tiny", "adamw", "dp", 0.999),
    ("resnet_micro", "sgd", "dp", None),
])
def test_builder_follows_the_config(devices, model, optimizer, strategy, b2):
    """The optimizer and the rule table are the ``Config``'s, whatever the
    model: no preset's, and no guess from the model's name."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib, trainer as trainer_lib)
    from pytorch_distributed_training_example_tpu.parallel import (
        sharding as sharding_lib)

    cfg = _lm_cfg(model, optimizer=optimizer, strategy=strategy,
                  grad_clip=0.0, num_classes=10, image_size=32)
    mesh = mesh_lib.build_mesh(cfg.mesh_config(), devices=devices)
    bundle = trainer_lib.build_model(cfg)
    program = trainer_lib.build_step_program(cfg, mesh, 10, bundle)
    assert program.rules == {"fsdp": sharding_lib.FSDP_RULES,
                             "dp": sharding_lib.DP_RULES}[strategy]

    # the state's optimizer tree: Adam's two moments, or SGD's one trace
    leaves = jax.tree.leaves(
        jax.eval_shape(program.init_state).opt_state,
        is_leaf=lambda s: hasattr(s, "nu") or hasattr(s, "trace"))
    assert any(hasattr(s, "nu") for s in leaves) == (optimizer == "adamw")
    assert any(hasattr(s, "trace") for s in leaves) == (optimizer == "sgd")
    if b2 is not None:  # read off the optimizer: one step on ones
        params = {"w": jnp.ones((2, 2))}
        _, state = program.tx.update(
            {"w": jnp.ones((2, 2))}, program.tx.init(params), params)
        nu = next(s.nu for s in jax.tree.leaves(
            state, is_leaf=lambda s: hasattr(s, "nu")) if hasattr(s, "nu"))
        np.testing.assert_allclose(nu["w"], 1 - b2, rtol=1e-5)
