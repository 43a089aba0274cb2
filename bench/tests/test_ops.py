"""A layer op defined only in a new file of the ops directory works through
``bench/model.py`` and ``harness.check_graph`` with no edit to either, and
an op with no module stops a run at set-up."""

import shutil
import time
import types

import numpy as np
import pytest

from bench import harness, model

AFFINE = '''"""``affine``: a per-channel scale and shift with an optional ReLU."""

import jax

FIELDS = ("relu",)
CONV_STACK = True


def out_shape(layer, ins):
    return ins[0]


def param_shapes(layer, ins):
    return (ins[0][1],), (ins[0][1],)


def init(keys, layer, ins):
    c = ins[0][1]
    return 1.0 + 0.1 * jax.random.normal(keys[0], (c,)), 0.1 * jax.random.normal(keys[1], (c,))


def flops(layer, ins, out):
    return 2 * out[0] * out[0] * out[1]


def forward(layer, params, xs, int8):
    scale, shift = params
    y = xs[0] * scale + shift
    return jax.nn.relu(y) if layer["relu"] else y
'''

CONFIG = {
    "input_size": 8, "in_channels": 3, "num_classes": 10, "compute_dtype": "bfloat16",
    "layers": [
        {"name": "conv1", "op": "conv", "inputs": ["image"], "K": 3, "S": 1, "pad": 1,
         "n_out": 4, "relu": False},
        {"name": "scale1", "op": "affine", "inputs": ["conv1"], "relu": True},
        {"name": "gap", "op": "global_pool", "inputs": ["scale1"]},
        {"name": "fc", "op": "dense", "inputs": ["gap"], "n_out": 10, "relu": False},
    ],
}
SEED = 2**32 + 3


@pytest.fixture
def ops_dir(tmp_path, monkeypatch):
    """The registry pointed at a copy of ``bench/ops`` with ``affine.py`` added."""
    for path in model.OPS_DIR.glob("*.py"):
        shutil.copy(path, tmp_path)
    (tmp_path / "affine.py").write_text(AFFINE)
    monkeypatch.setattr(model, "OPS_DIR", tmp_path)
    return tmp_path


def graph(relu=True):
    """A program graph stub with the configuration's layers."""
    node = types.SimpleNamespace
    return types.SimpleNamespace(name="toy", input_size=8, in_channels=3, nodes=[
        node(name="image", op="input", inputs=()),
        node(name="conv1", op="conv", inputs=("image",), K=3, S=1, pad=1, n_out=4, relu=False),
        node(name="scale1", op="affine", inputs=("conv1",), relu=relu),
        node(name="gap", op="global_pool", inputs=("scale1",)),
        node(name="fc", op="dense", inputs=("gap",), n_out=10, relu=False),
    ])


def numpy_logits(params, images):
    """The toy network in float64 NumPy."""
    w, b = (np.asarray(a, np.float64) for a in params["conv1"])
    x = np.pad(images.astype(np.float64), ((0, 0), (1, 1), (1, 1), (0, 0)))
    y = sum(x[:, i:i + 8, j:j + 8] @ w[i, j] for i in range(3) for j in range(3)) + b
    scale, shift = (np.asarray(a, np.float64) for a in params["scale1"])
    y = np.maximum(y * scale + shift, 0).mean(axis=(1, 2))
    fw, fb = (np.asarray(a, np.float64) for a in params["fc"])
    return y @ fw + fb


def test_new_op_runs_through_the_benchmark(ops_dir):
    assert model.shapes(CONFIG) == {"image": (8, 3), "conv1": (8, 4), "scale1": (8, 4),
                                    "gap": (0, 4), "fc": (0, 10)}
    params = model.init_params(CONFIG, SEED)
    assert [a.shape for a in params["scale1"]] == [(4,), (4,)]
    again = model.init_params(CONFIG, SEED)["scale1"][0]
    assert np.array_equal(np.asarray(params["scale1"][0]), np.asarray(again))

    images = model.make_images(CONFIG, SEED, 3)
    reference = model.logits_in_blocks(CONFIG, params, images, block=2)
    want = numpy_logits(params, images)
    assert np.abs(reference - want).max() <= 1e-5 * np.abs(want).max()
    control = model.logits_in_blocks(CONFIG, params, images, int8=True, block=2)
    assert 0 < model.logit_rms_error(control, reference).max() < 0.2

    conv, affine, dense = 2 * 3 * 3 * 3 * 4 * 64, 2 * 64 * 4, 2 * 4 * 10
    assert model.flops_per_image(CONFIG) == conv + affine + dense
    assert model.flops_per_image(CONFIG, ("affine",)) == affine
    # the conv stack ends at the affine layer and reads its parameters
    assert model.conv_stack_least_bytes(CONFIG, 1) == 2 * ((108 + 4 + 4 + 4) + 192 + 256)
    peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    assert model.conv_stack_least_seconds(CONFIG, 2, peak) == (2.0 * (conv + affine), "compute")

    harness.check_graph(graph(), CONFIG)
    with pytest.raises(SystemExit):
        harness.check_graph(graph(relu=False), CONFIG)


def test_op_without_a_module_is_refused(ops_dir, capsys):
    (ops_dir / "affine.py").unlink()
    for call in (lambda: model.shapes(CONFIG), lambda: model.init_params(CONFIG, SEED),
                 lambda: harness.check_graph(graph(), CONFIG)):
        with pytest.raises(harness.BenchError):
            call()
        assert f"no module {ops_dir / 'affine.py'}" in capsys.readouterr().err

    (ops_dir / "affine.py").write_text('FIELDS = ("relu",)\n')
    with pytest.raises(harness.BenchError):
        model.shapes(CONFIG)
    assert "does not define ['CONV_STACK'," in capsys.readouterr().err


def test_run_stops_at_set_up_on_an_op_without_a_module(capsys):
    """A configuration naming an op that ``bench/ops`` lacks: the run ends
    before anything is served."""
    config = dict(model.load_config("resnet18-bf16"), input_size=32)
    config["layers"] = [dict(layer, op="dwconv") if layer["name"] == "conv1" else layer
                        for layer in config["layers"]]
    with pytest.raises(harness.BenchError):
        harness.run_cell("resnet18-bf16.offline", SEED, 1.0, False, time.perf_counter(),
                         config=config, require_tpu=False, say=lambda s: None)
    assert f"no module {harness.BENCH_DIR / 'ops' / 'dwconv.py'}" in capsys.readouterr().err
