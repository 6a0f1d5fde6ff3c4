"""At toy sizes on the CPU, in float32: a few ``FedSim`` rounds of each
family equal the benchmark's plain reference tightly; the same comparison
fails when the reference's place is taken by the control (the reference with
its matrix products' operands rounded to the precision below the one the
configuration states); and the FLOPs functions give their closed forms.

Nothing here describes a TPU topology; the file is safe under xdist.
"""

import jax
import pytest

from benchmark import run as benchrun
from benchmark.families import resnet as resnet_family
from benchmark.families import transformer_lm as lm_family
from benchmark.reference import fedavg, precision

from ._tiny import TOY_BN, tiny_cell

CELLS = (TOY_BN, "resnet18gn_dev10", "cgpt13b_silo2")
# float32 against float32 on one backend: what is left is summation order
TIGHT = {"loss_gap": 1e-5, "norm_gap": 1e-4, "update_rel_l2": 1e-3, "eval_test_loss_gap": 1e-5}
SEED = 2 ** 31 + 77


def program_check(cell, seed):
    """The check rounds through FedSim as ``benchmark.run.run`` drives them."""
    sim, variables = benchrun.build_sim(cell, seed, jax.devices()[:1])
    return benchrun.program_check(sim, variables, cell)[0]


@pytest.fixture(scope="module", params=CELLS)
def checked(request):
    cell = tiny_cell(request.param)
    return cell, program_check(cell, SEED)


def within(numbers, limits):
    return all(v <= limits[k.split(".")[0]] for k, v in numbers.items()
               if k.split(".")[0] in limits)


def test_program_round_equals_the_plain_reference(checked):
    cell, check = checked
    ref = benchrun.reference_check(cell, SEED, check["rounds"], check["shapes"])
    numbers = benchrun.compare(check, ref)
    assert check["losses"], "no local training loss was compared"
    assert within(numbers, TIGHT), numbers
    limits = {k: v for k, v in TIGHT.items() if k in cell["config"]["check"]["limits"]}
    assert benchrun.judge(numbers, limits)


@pytest.mark.parametrize("control", ["bf16", "fp8"])
def test_lower_precision_control_fails(checked, control):
    """The control in the program's place: its numbers must break the tight
    limits, by the update's distance at least."""
    cell, check = checked
    ref = benchrun.reference_check(cell, SEED, check["rounds"], check["shapes"])
    stand_in = benchrun.reference_check(cell, SEED, check["rounds"], check["shapes"],
                                        precision=control)
    numbers = benchrun.compare(stand_in, ref)
    assert not within(numbers, TIGHT), numbers
    worst = max(v for k, v in numbers.items() if k.startswith("update_rel_l2"))
    assert worst > 3 * TIGHT["update_rel_l2"], numbers
    assert not benchrun.judge(numbers, {"update_rel_l2": TIGHT["update_rel_l2"]})


def test_judge_needs_every_limit_met_and_produced():
    assert benchrun.judge({"a.x": 0.1, "a.y": 0.2, "b": 3.0}, {"a": 0.5})
    assert not benchrun.judge({"a.x": 0.1, "a.y": 0.7}, {"a": 0.5})
    assert not benchrun.judge({"a": float("nan")}, {"a": 0.5})
    assert not benchrun.judge({"b": 0.0}, {"a": 0.5})  # a limit with nothing to hold


def test_update_numbers_by_hand():
    old = {"params": {"w": jax.numpy.zeros(4), "tiny": jax.numpy.zeros(2)}}
    ref = {"params": {"w": jax.numpy.array([3.0, 4.0, 0.0, 0.0]), "tiny": jax.numpy.array([1e-9, 0.0])}}
    prog = {"params": {"w": jax.numpy.array([3.0, 4.0, 0.0, 0.5]), "tiny": jax.numpy.array([2e-9, 0.0])}}
    out = fedavg.update_numbers(old, prog, ref)
    # leaf norms: w 5 vs sqrt(25.25); tiny 1e-9 vs 2e-9, held against the median leaf (2.5)
    assert out["norm_gap"] == pytest.approx((25.25 ** 0.5 - 5.0) / 5.0, rel=1e-4)
    assert out["update_rel_l2.params"] == pytest.approx(0.5 / 5.0, rel=1e-4)
    same = fedavg.update_numbers(old, ref, ref)
    assert same["norm_gap"] == 0.0 and same["update_rel_l2.params"] == 0.0


def test_sgd_step_orders_decay_then_momentum():
    p, m = fedavg.sgd_step({"w": jax.numpy.array(2.0)}, {"w": jax.numpy.array(1.0)},
                           {"w": jax.numpy.array(0.5)}, 0.1, 0.01, 0.9)
    # g = 0.5 + 0.01 * 2 = 0.52; m = 0.9 * 1 + 0.52 = 1.42; p = 2 - 0.142
    assert float(m["w"]) == pytest.approx(1.42) and float(p["w"]) == pytest.approx(1.858)


def test_precision_rounds_operands_and_the_incoming_gradient():
    x = jax.numpy.array([1.0, 1.0 + 2 ** -10, 300.0])
    assert float(precision.round_to(x, "bf16")[1]) == 1.0  # 8 bits of mantissa
    fp8 = precision.round_to(x, "fp8")
    assert float(fp8[2]) == pytest.approx(300.0, rel=0.07) and float(fp8[1]) != float(x[1])
    with pytest.raises(ValueError):
        precision.round_to(x, "int3")
    mul = lambda a, b: a * b  # noqa: E731
    assert precision.product(mul, "f32") is mul
    w = jax.numpy.array([1.0, 1.0, 1.0])
    low = precision.product(mul, "bf16")
    assert float(low(x, w)[1]) == 1.0  # the operand was rounded on the way in
    # d/dx sum(g * x * w) = g * w: g is rounded to bf16 on the way back
    g = jax.numpy.array([1.0 + 2 ** -10, 1.0, 1.0])
    grad = jax.grad(lambda a: (g * low(a, w)).sum())(x)
    assert float(grad[0]) == 1.0 and float(jax.grad(lambda a: (g * mul(a, w)).sum())(x)[0]) != 1.0


def test_lm_flops_closed_forms():
    """ISSUE 24: 0.327 L + 0.617 GFLOP a token at T 2048; 95 TFLOP for
    chip_smoke.py's round (2 clients x 4 steps x batch 4 x T 1024, D 2048, L 8, V 32000)."""
    cerebras = {"n_embd": 2048, "n_layer": 0, "vocab_size": 50257}
    head = lm_family.train_flops_per_token(cerebras, 2048)
    per_layer = lm_family.train_flops_per_token({**cerebras, "n_layer": 1}, 2048) - head
    assert head / 1e9 == pytest.approx(0.617, abs=1e-3)
    assert per_layer / 1e9 == pytest.approx(0.327, abs=1e-3)
    smoke = {"n_embd": 2048, "n_layer": 8, "vocab_size": 32000}
    traffic = {"clients_per_round": 2, "local_steps": 4, "batch_size": 4, "seq_len": 1024}
    assert lm_family.flops_per_round(smoke, traffic) / 1e12 == pytest.approx(95.0, abs=0.5)
    assert lm_family.samples_per_round(smoke, traffic) == 32768


def test_resnet_flops_closed_forms():
    """bench.py's ResNet-56 figure (its train FLOPs an image over 3), and
    ISSUE 24's 0.62 GFLOP forward for ResNet-18 at 24x24 (1.86 TFLOP a round
    of 1,000 images)."""
    r56 = {"image_hw": 32, "image_channels": 3, "stage_channels": [16, 32, 64],
           "blocks_per_stage": 9, "num_classes": 10}
    fl = 2 * 32 * 32 * 9 * 3 * 16 + 2 * 64 * 10
    for si, (cin, cout, hw) in enumerate([(16, 16, 32), (16, 32, 16), (32, 64, 8)]):
        for b in range(9):
            c_in = cin if b == 0 else cout
            fl += 2 * hw * hw * 9 * c_in * cout + 2 * hw * hw * 9 * cout * cout
            fl += 2 * hw * hw * c_in * cout if (b == 0 and si > 0) else 0
    assert resnet_family.forward_flops_per_image(r56) == fl
    cell = benchrun.load_cell("resnet18gn_dev10")
    fwd = resnet_family.forward_flops_per_image(cell["config"]["model"])
    assert fwd / 1e9 == pytest.approx(0.62, abs=0.01)
    assert resnet_family.samples_per_round(cell["config"], cell["traffic"]) == 1000
    assert resnet_family.flops_per_round(cell["config"], cell["traffic"]) / 1e12 == pytest.approx(
        1.86, abs=0.03)
    silo = tiny_cell(TOY_BN)  # unequal shards: 48 + 16 + 32 images, a test set of 32
    assert resnet_family.samples_per_round(silo["config"], silo["traffic"]) == 96
    assert resnet_family.eval_samples(silo["config"], silo["traffic"]) == 128
