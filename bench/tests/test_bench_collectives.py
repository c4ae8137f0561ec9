"""``collective_ms`` on a small hand-made trace of two devices, with op
names in the form the chip's profiler gives them (the HLO instruction)."""
from types import SimpleNamespace

import pytest

from bench.lib import harness as H

READER = H.load_module("metrics", "collective_ms")
AR = ("%all-reduce-start.3 = f32[576,1536]{1,0:T(8,128)} all-reduce-start("
      "f32[576,1536]{1,0:T(8,128)} %fusion.12), channel_id=4, replica_groups"
      "={{0,1}}, use_global_device_ids=true, to_apply=%add.1")
AG = ("%all-gather.7 = f32[30,576,1536]{2,1,0:T(8,128)} all-gather(f32[30,144"
      ",1536]{2,1,0:T(8,128)} %param.2), channel_id=9, replica_groups=[1,2]<="
      "[2], dimensions={1}")
RS = ("%reduce-scatter.1 = f32[144,1536]{1,0} reduce-scatter(f32[576,1536]"
      "{1,0} %fusion.3), channel_id=2, dimensions={0}, to_apply=%add.2")
A2A = "%all-to-all.2 = f32[4,8]{1,0} all-to-all(f32[4,8]{1,0} %copy.1)"
CP = ("%collective-permute-done.5 = f32[8]{0} collective-permute-done(f32[8]"
      "{0} %collective-permute-start.5)")
FUSION = ("%fusion.81 = f32[576,1536]{1,0} fusion(f32[576,1536]{1,0} "
          "%all-reduce-done.3, f32[] %param.1), kind=kLoop, calls=%fused.1")


def ctx(devices, steps=2, lo=0.0, hi=1e10):
    return SimpleNamespace(trace={"devices": devices, "host": []}, lo=lo,
                           hi=hi, steps=steps)


@pytest.mark.parametrize("op", [AR, AG, RS, A2A, CP])
def test_each_kind_of_collective_is_found_by_its_opcode(op):
    assert READER.is_collective(op)


@pytest.mark.parametrize("op", [
    FUSION, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %param.0), kind=kLoop",
    "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.4)",
    "%add.9 = f32[] add(f32[] %all-reduce.3, f32[] %all-gather.1)"])
def test_an_op_that_only_reads_a_collective_is_not_one(op):
    assert not READER.is_collective(op)


def test_collectives_are_averaged_over_chips_and_divided_by_steps():
    devices = {
        "/device:TPU:0": [(AR, 0, 4e6), (FUSION, 4e6, 9e6), (AG, 10e6, 12e6)],
        "/device:TPU:1": [(AR, 0, 6e6), (RS, 20e6, 22e6), (FUSION, 30e6,
                                                           40e6)],
    }
    # chip 0: 6 ms, chip 1: 8 ms of collectives -> 7 ms over 2 steps
    assert READER.read(ctx(devices)) == pytest.approx(3.5)
    # clipped to the window: chip 0 keeps 1 ms of AR, chip 1 3 ms
    assert READER.read(ctx(devices, steps=1, lo=3e6, hi=9e6)) == \
        pytest.approx(2.0)


def test_no_collective_reads_nothing():
    devices = {"/device:TPU:0": [(FUSION, 0, 1e6)],
               "/device:TPU:1": [(FUSION, 0, 1e6)]}
    assert READER.read(ctx(devices)) is None
    assert READER.read(ctx({})) is None
    assert READER.read(ctx({"/device:TPU:0": [(AR, 0, 1e6)]}, steps=0)) \
        is None
