"""The toy shape ``mlp-epochs`` rehearses at on the CPU, and the five
controls its ``correct`` is held to, planted in the program
(``harp_tpu.models.mlp``) for the length of a ``with``: the tier-1 cases
of ``test_mlp_cell.py`` plant them at the toy size, and the builder's
chip script planted the same ones at the cell's size (PERF.md section 6
has what each read there)."""

import dataclasses
from unittest import mock

import jax
import optax

from harp_tpu.models import mlp

# the in-test override (``harness.run_cell(override=...)``): the shape
# only, and a block short enough for the CPU.  A CPU's float32 dots are
# exact where the chip's are one bf16 pass, so the toy size gets toy
# bands: the program reads 2e-5 and under in all six there
TINY = {"data": {"n_per_chip": 2048, "d": 16, "classes": 4},
        "knobs": {"sizes": [16, 32, 16, 4], "batch_per_worker": 64},
        "work": {"sizes": [16, 32, 16, 4]},
        "traffic": {"steps": 2, "trace_seconds": 0.2},
        "reference": {"step_rel_limit": 1e-3, "step_as_stated_rel_limit": 1e-3,
                      "logits_rel_limit": 1e-3,
                      "logits_as_stated_rel_limit": 1e-3,
                      "block_rel_limit": 1e-3, "block_loss_rel_limit": 1e-3}}


def half_precision():
    """bf16 activations under a configuration that states float32."""
    true = mlp.forward
    return mock.patch.object(mlp, "forward", lambda params, x, cfg: true(
        params, x, dataclasses.replace(cfg, half_precision=True)))


def bf16_parameters():
    """The parameters pass through bfloat16 after every update."""
    true = optax.apply_updates

    def rounded(params, updates):
        # not a pair of casts: XLA may keep the excess precision of a
        # float32 -> bfloat16 -> float32 round trip, and on the chip does
        return jax.tree.map(
            lambda p: jax.lax.reduce_precision(p, exponent_bits=8,
                                               mantissa_bits=7),
            true(params, updates))

    return mock.patch.object(optax, "apply_updates", rounded)


def lr_halved():
    true = mlp.make_optimizer
    return mock.patch.object(mlp, "make_optimizer", lambda cfg: true(
        dataclasses.replace(cfg, lr=cfg.lr / 2)))


def epochs_program_lr_halved():
    """Half the learning rate in the program the window times
    (``make_epoch_fn``) and nowhere else: ``train_batch`` and ``predict``
    stay sound."""
    true = mlp.make_epoch_fn
    return mock.patch.object(
        mlp, "make_epoch_fn", lambda mesh, cfg, *a, **k: true(
            mesh, dataclasses.replace(cfg, lr=cfg.lr / 2), *a, **k))


def bias_gradient_left_out():
    """The first layer's bias gets no gradient."""
    true = mlp.loss_fn

    def loss_fn(params, x, y, cfg):
        first = dict(params[0], b=jax.lax.stop_gradient(params[0]["b"]))
        return true([first, *params[1:]], x, y, cfg)

    return mock.patch.object(mlp, "loss_fn", loss_fn)


CONTROLS = {"half_precision": half_precision,
            "bf16_parameters": bf16_parameters,
            "lr_halved": lr_halved,
            "bias_gradient_left_out": bias_gradient_left_out,
            "epochs_program_lr_halved": epochs_program_lr_halved}
