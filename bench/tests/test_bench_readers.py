"""Each per-layer reader on a small hand-made trace, with op names in the
form the chip's profiler gives them."""
from types import SimpleNamespace

import pytest

from bench.lib import flops
from bench.lib import harness as H

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LM = H.load_json(H.BENCH, "configs", "smollm-135m.json")
TRAIN = H.load_json(H.BENCH, "traffic", "kfac-lm-1x2048.json")
FU = ("%vmap__.2 = f32[30,1536,1536]{2,1,0:T(8,128)} custom-call(f32[2]{0:T(128)"
      "S(1)} %pad_maximum_fusion.12, f32[30,4096,1536]{2,1,0:T(8,128)} %bitcast.35"
      ", f32[30,4096,1536]{2,1,0:T(8,128)} %bitcast.35, f32[30,1536,1536]{2,1,0:T("
      "8,128)} %state_factors__blk0_mlp_gate____g__.1), custom_call_target=\"tpu_c"
      "ustom_call\", operand_layout_constraints={f32[2]{0}, f32[30,4096,1536]{2,1"
      ",0}}")


def ctx(devices, host=(), **kw):
    tr = {"devices": {"/device:TPU:0": list(devices)}, "host": list(host)}
    base = dict(trace=tr, lo=0, hi=1e10, peaks=PEAKS, n_devices=1,
                bench=H.BENCH,
                reduced={"busy_s": 2.5, "window_s": 10.0})
    base.update(kw)
    return SimpleNamespace(**base)


def test_idle_share():
    for name in ("idle_share.train", "idle_share.ae"):
        assert H.load_module("metrics", name).read(ctx([])) == 75.0


def test_stage_spans_per_step():
    host = [("kfac/estimate_stats", 0, 3e8), ("kfac/estimate_stats", 5e8,
                                              8e8),
            ("kfac/scheduled_inverse_refresh", 3e8, 4e8)]
    c = ctx([], host, steps=2)
    assert H.load_module("metrics", "kfac_stats_ms").read(c) == \
        pytest.approx(300.0)
    assert H.load_module("metrics", "kfac_refresh_ms").read(c) == \
        pytest.approx(50.0)
    assert H.load_module("metrics", "kfac_stats_ms.ae").read(c) == \
        pytest.approx(300.0)
    assert H.load_module("metrics", "kfac_refresh_ms.ae").read(c) == \
        pytest.approx(50.0)
    assert H.load_module("metrics", "kfac_stats_ms").read(
        ctx([], [], steps=2)) is None


def test_factor_update_roofline_from_each_calls_shapes():
    ops = [(FU, i * 1e8, i * 1e8 + 5e7) for i in range(4)]
    ops.append(("%fusion.81 = f32[30,1536,1536]{1,2,0} fusion(f32[2]{0})",
                5e8, 9e8))
    # custom calls of other forms: one square operand; another target
    ops.append(("%custom-call.5 = f32[576,576]{0,1:T(8,128)} custom-call("
                "f32[576,576]{1,0:T(8,128)} %copy.3), custom_call_target="
                "\"AllocateBuffer\"", 9e8, 9.5e8))
    ops.append((FU.replace("tpu_custom_call", "other_call"), 9.5e8, 9.9e8))
    f, b = flops.factor_update(4096, 1536, stack=30)
    want = 100.0 * 4 * max(f / PEAKS["flops_per_s"],
                           b / PEAKS["hbm_bytes_per_s"]) / 0.2
    reader = H.load_module("metrics", "factor_update_roofline")
    assert reader.read(ctx(ops)) == pytest.approx(want)
    assert reader.read(ctx(ops[4:])) is None
    # as the compiler prints it: operands by name, shapes in the constraints
    hlo = ("%vmap__.2 = f32[30,1536,1536]{2,1,0:T(8,128)} custom-call(%pad_max"
           "imum_fusion.12, %bitcast.50, %bitcast.50, %state_factors.1), custom_"
           "call_target=\"tpu_custom_call\", operand_layout_constraints={f32[2]{0"
           "}, f32[30,2048,1536]{2,1,0}, f32[30,2048,1536]{2,1,0}}")
    f2, b2 = flops.factor_update(2048, 1536, stack=30)
    assert reader.read(ctx([(hlo, 0, 1e7)])) == pytest.approx(
        100.0 * max(f2 / PEAKS["flops_per_s"], b2 / PEAKS["hbm_bytes_per_s"])
        / 0.01)
    one = FU.replace("30,", "").replace("4096", "2048")      # unstacked
    f1, b1 = flops.factor_update(2048, 1536, stack=1)
    assert reader.read(ctx([(one, 0, 1e7)])) == pytest.approx(
        100.0 * max(f1 / PEAKS["flops_per_s"], b1 / PEAKS["hbm_bytes_per_s"])
        / 0.01)


def test_mfu_train_counts_model_flops_per_step():
    c = ctx([], config=LM, traffic=TRAIN, steps=10, wall=5.0)
    want = 100.0 * 10 * flops.llama_train_flops(LM, 1, 2048) / (5.0 * 197e12)
    assert H.load_module("metrics", "mfu.train").read(c) == pytest.approx(want)


def test_mfu_ae_counts_the_mirrored_autoencoder():
    cfg = H.load_json(H.BENCH, "configs", "mnist-autoencoder.json")
    traffic = H.load_json(H.BENCH, "traffic", "kfac-ae-8192.json")
    c = ctx([], config=cfg, traffic=traffic, steps=4, wall=2.0)
    dims = [784, 1000, 500, 250, 30, 250, 500, 1000, 784]
    want = 100.0 * 4 * flops.mlp_train_flops(dims, 8192) / (2.0 * 197e12)
    assert H.load_module("metrics", "mfu.ae").read(c) == pytest.approx(want)


@pytest.mark.parametrize("config,traffic", [
    ("smollm-135m", "kfac-lm-1x2048"), ("mnist-autoencoder", "kfac-ae-8192")])
def test_mfu_train_is_the_family_formula_bit_for_bit(config, traffic):
    """The adapter's ``train_flops`` gives what the reader's family
    branches gave before the adapters: the same float, not a near one."""
    cfg = H.load_json(H.BENCH, "configs", f"{config}.json")
    tr = H.load_json(H.BENCH, "traffic", f"{traffic}.json")
    data = tr["data"]
    if "encoder" in cfg:
        enc = list(cfg["encoder"])
        per_step = flops.mlp_train_flops(enc + enc[-2::-1], data["batch"])
    else:
        per_step = flops.llama_train_flops(cfg, data["batch"], data["seq"])
    c = ctx([], config=cfg, traffic=tr, steps=37, wall=20.013)
    want = 100.0 * per_step * 37 / (20.013 * 1 * 197e12)
    assert H.load_module("metrics", "mfu.train").read(c) == want
    assert H.load_module("metrics", "mfu.ae").read(c) == want
