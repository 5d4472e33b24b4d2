"""Each configuration's plain reference against the system on the CPU, at a
small input and batch: logits, loss and the three named gradients, through
the same entry code and the same comparison as a run on the chip.  The
pattern a later model PR copies: one case per configuration."""

import copy
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.harness import check, manifest  # noqa: E402

# (cell, input shape at the small size, check batch); inception_v3's fixed
# 8x8 average pool allows no input but 299x299
CASES = [
    ("resnet50_train_bs128", [64, 64, 3], 4),
    ("inception3_train_bs128", [3, 299, 299], 2),
]


@pytest.mark.parametrize("cell_name,shape,batch", CASES,
                         ids=[c[0] for c in CASES])
def test_system_agrees_with_its_plain_reference(cell_name, shape, batch):
    import jax

    cell = manifest.Manifest(REPO).cell(cell_name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["input"]["shape"] = shape
    cell.config["check_batch"] = batch
    reference = cell.reference()
    ctx = run.Context(cell, seed=11, devices=jax.devices()[:1])
    session = cell.entry().build(ctx)
    system = session.system_outputs(reference)
    lines = []
    assert check.against_reference(reference, cell.config, system,
                                   lines.append), "\n".join(lines)
    assert len(lines) == 5      # logits, loss, three gradients

    if cell_name != CASES[0][0]:
        return
    # the comparison is not vacuous: a gradient that lost a term fails
    # (shown once: the second evaluation of the reference is the cost)
    name = cell.config["check_gradients"][0]
    system["gradients"][name] = system["gradients"][name] * 0.5
    assert not check.against_reference(reference, cell.config, system,
                                       lines.append)


def test_flops_functions_count_what_the_papers_state():
    """ResNet-50: 3.8e9 multiply-adds forward in the paper's table 1 (the
    served model strides its first 1x1, 3.86e9); Inception v3: about 5.7e9.
    Training is three times the forward pass."""
    m = manifest.Manifest(REPO)
    for cell_name, lo, hi in (("resnet50_train_bs128", 3.7e9, 4.0e9),
                              ("inception3_train_bs128", 5.5e9, 6.0e9)):
        cell = m.cell(cell_name)
        flops = cell.reference().flops_per_sample(
            cell.config["architecture"], cell.config["input"]["shape"])
        assert lo < flops / 6 < hi, (cell_name, flops)
