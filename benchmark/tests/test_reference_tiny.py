"""Each family's plain reference against the program's ``loss_fn`` at
the tiny presets, on the CPU.

Tolerance. The program computes in bf16 (8 bits of mantissa: one value
is off by up to 2**-9 = 2e-3 relative), the reference in float32 at
precision "highest". The loss is a mean over the batch's targets, which
averages rounding out. At seeded initial weights it also sits near
log(classes) whatever the model computes, so a WRONG term moves it by
little too (dropping the transformer's MLP: 6e-5 relative here): the
tolerance has to sit just above the rounding actually measured.
Transformer, 8 x 127 targets: measured up to 5.7e-6 over four seeds, so
3e-5. ResNet, 8 targets, batch-norm over 8 rows amplifying rounding:
measured up to 3.0e-3, so 1e-2. On the chip each cell's file carries
its own tolerance, set the same way from the chip's measurement."""

import json
import os

import jax
import pytest

from benchmark import manifest
from benchmark.common import key_seed

CASES = [("gpt2s_epoch", 3e-5), ("resnet50_epoch", 1e-2)]


def _tiny(cell_name):
    return manifest.cell(cell_name, rehearse=True)


@pytest.mark.parametrize("cell_name,rtol", CASES)
def test_reference_matches_program_loss(cell_name, rtol):
    import importlib

    cell = _tiny(cell_name)
    model, seed = cell["model"], 2 ** 31 + 11
    p = cell["family"].pieces(model, cell["workload"], seed)
    init = p.model_init(jax.random.key(key_seed(seed)))
    if p.stateful:
        program, _ = p.loss_fn(init[0], init[1], p.batch)
    else:
        program = p.loss_fn(init, p.batch)
    reference = importlib.import_module(
        f"benchmark.families.{model['family']}_reference").loss(
            init, p.batch, model)
    assert abs(float(program) - reference) <= rtol * abs(reference), (
        float(program), reference)


def test_gpt_reference_sees_a_dropped_term():
    """The comparison has teeth: without the MLP's output the loss
    moves by more than the tolerance (see the note on top)."""
    from benchmark.families import gpt_reference

    cell = _tiny("gpt2s_epoch")
    p = cell["family"].pieces(cell["model"], cell["workload"], 5)
    init = p.model_init(jax.random.key(5))
    whole = gpt_reference.loss(init, p.batch, cell["model"])
    blocks = dict(init["blocks"], w_out=init["blocks"]["w_out"] * 0)
    assert abs(gpt_reference.loss(dict(init, blocks=blocks), p.batch,
                                  cell["model"]) - whole) > 3e-5 * whole


def test_same_seed_same_batch_and_large_seed():
    cell = _tiny("gpt2s_epoch")
    a = cell["family"].pieces(cell["model"], cell["workload"], 2 ** 31 + 5)
    b = cell["family"].pieces(cell["model"], cell["workload"], 2 ** 31 + 5)
    c = cell["family"].pieces(cell["model"], cell["workload"], 2 ** 31 + 6)
    assert (a.batch == b.batch).all() and not (a.batch == c.batch).all()


def test_flops_per_sample_of_the_real_configurations():
    with open(os.path.join(manifest.HERE, "configs", "resnet50.json")) as f:
        resnet50 = json.load(f)
    from benchmark.families import gpt, resnet

    plan, features = resnet.conv_plan(resnet50, 224)
    assert len(plan) == 53 and features == 2048
    # 4.09 G multiply-adds forward (the figure torchvision gives)
    assert resnet.flops_per_sample(resnet50, {"hw": 224}) == pytest.approx(
        3 * 2 * 4.09e9, rel=0.02)
    gpt2 = manifest.config_file("gpt2_small")
    # 6 x 124M parameters x 1024 tokens, and attention's few percent
    assert gpt.flops_per_sample(gpt2, {"seq": 1024}) == pytest.approx(
        6 * 124.4e6 * 1024, rel=0.08)
