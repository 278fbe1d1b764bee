"""The reduction from a profiler trace to the per-layer numbers: on a
small trace recorded on the chip (``record_trace.py``), and on the
pieces by hand."""

import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tree_lstm_2steps")

# Operation names as the chip's trace gives them (shortened operands).
FWD = ('%closed_call.14 = f32[65537,1,1024]{2,1,0:T(1,128)} custom-call('
       's32[8192]{0} %reshape.126, s32[4096]{0} %bitcast.119, s32[1]{0} '
       '%mul.490, f32[65537,1,1024]{2,1,0} %gte.1), custom_call_target='
       '"tpu_custom_call", output_to_operand_aliasing={{}: (3, {})}')
BWD = ('%closed_call.15 = (f32[65537,1,1024]{2,1,0}, f32[2,4096,1,1024]'
       '{3,2,1,0}) custom-call(s32[8192]{0} %reshape.127, s32[4096]{0} '
       '%bitcast.118, s32[1]{0} %mul.491, s32[8192]{0} %a), '
       'custom_call_target="tpu_custom_call", '
       'output_to_operand_aliasing={{0}: (7, {})}')
SCATTER = ('%closed_call.6 = f32[4097,1,1024]{2,1,0} custom-call(s32[256]'
           '{0} %bitcast.7, f32[4097,1,1024]{2,1,0} %reshape.8, f32[256,1,'
           '1024]{2,1,0} %x), custom_call_target="tpu_custom_call", '
           'output_to_operand_aliasing={{}: (1, {})}')
FUSION = '%fusion.3 = f32[8193,2048]{1,0} fusion(s32[65536]{0} %g), kind=kLoop'


def test_kernels_told_apart_by_operands():
    assert tr.kernel_of(FWD) == "_megastep_kernel"
    assert tr.kernel_of(BWD) == "_bwd_megastep_kernel"
    assert tr.kernel_of(SCATTER) is None
    assert tr.kernel_of(FUSION) is None
    assert tr.short_name(FUSION) == "%fusion.3 = fusion"
    assert tr.short_name(SCATTER) == "%closed_call.6 = custom-call"


def test_union_and_own_times():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    # a loop of 10 ns holding two ops of 3 and 2 ns: 5 ns its own
    own = dict(tr._own_times([("loop", 0, 10), ("a", 1, 4), ("b", 6, 8)]))
    assert own == {"loop": 5e-9, "a": 3e-9, "b": 2e-9}


def test_innermost_host_label():
    spans = [(0, 100, "bench.window"), (10, 40, "cb.tick"),
             (12, 20, "cb.plan"), (50, 90, "cb.tick")]
    assert tr._innermost(spans, [5, 15, 30, 45, 60, 95]) == [
        "bench.window", "cb.plan", "cb.tick", "bench.window", "cb.tick",
        "bench.window"]


def test_recorded_chip_trace():
    import jax
    with open(DATA + ".json") as f:
        want = json.load(f)
    red = tr.reduce(jax.profiler.ProfileData.from_file(DATA + ".xplane.pb"))
    launches = want["steps"] * want["levels"]
    assert tr.kernel_seconds(red, "_megastep_kernel")[0] == launches
    assert tr.kernel_seconds(red, "_bwd_megastep_kernel")[0] == launches
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    own = sum(t for _c, t in red["ops"].values())
    assert own == pytest.approx(red["busy_s"], rel=1e-6)
    b = red["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # The window's annotation was found: every gap falls inside it.
    assert {n for n, _t in b["idle_gaps"]} == {"bench.window"}
    assert b["device_ops"][0][0] in ("_bwd_megastep_kernel",
                                     "_megastep_kernel")
