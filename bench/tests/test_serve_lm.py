"""The chat-scoring cell (``loops/serve_lm.py``) as a whole run at a
small size on the CPU, its control, and its per-layer readers on a
record of the shape the loop writes."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite4_h_micro.serve_chat"

#: A period of the published pattern at toy widths.
SMALL_CFG = {"hidden_size": 64, "vocab_size": 512,
             "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "shared_intermediate_size": 128,
             "vertex_args": {"kv_block": 8, "max_len": 48}}
SMALL_TRAFFIC = {"rate_per_s": 12.0,
                 "prompt": {"mu": 2.5, "sigma": 0.6, "min": 4, "max": 32},
                 "answer": {"mu": 1.8, "sigma": 0.5, "min": 2, "max": 16},
                 "engine": {"num_rows": 16, "frontier_width": 8,
                            "kv_tokens": 8 * 48},
                 "check_requests": 8, "check_block": 4}


def _run(control=False):
    import run
    return run.run_cell(CELL, 3_000_000_019, 1.0, False,
                        cfg_override=SMALL_CFG,
                        traffic_override=SMALL_TRAFFIC, require_tpu=False,
                        control=control)


def test_result_line_and_control():
    result, checks, out = _run(control=True)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) == want == {"serve_p50_ms", "setup_s"}
    assert [c[0] for c in checks] == ["logit_rms_gap"]
    # the reference with three-pass bfloat16 products fails the limit
    limit = checks[0][2]
    assert out["control_values"]["logit_rms_gap"] > limit
    assert out["record"]["state_rows_total"] > 0
    json.dumps(result)


def test_sample_spans_the_lengths():
    import numpy as np
    from loops import serve_lm
    lengths = np.arange(1000) % 97 + 3
    ok = np.ones(1000, bool)
    ok[::5] = False
    pick = serve_lm.sample(lengths, ok, 64, np.random.default_rng(1))
    assert len(set(pick)) == 64 and ok[pick].all()
    got = np.sort(lengths[pick])
    assert got[0] <= 5 and got[-1] >= 96


def _load(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "bench", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_readers():
    import costs_hybrid
    ssm = {"layers": 9, "heads": 64, "head_dim": 64, "d_state": 128,
           "d_conv": 4}
    # one layer's state row: 64*64*128 + 3*4352 floats
    assert costs_hybrid.row_floats(ssm) == 537344
    rec = {"state_rows_live": 300, "state_rows_total": 400,
           "profile_lanes": 6400, "profile_starts": 0, "profile_ticks": 100,
           "ssm": ssm, "device_kind": "TPU v5 lite",
           "trace": {"ops": {"%ssm_step.3 = custom-call": [900, 0.9],
                             "%fusion.1 = fusion": [10, 1.0]}}}
    assert _load("serve.state_row_occupancy").read(rec) == pytest.approx(75.0)
    share = _load("ssm_step_roofline").read(rec)
    # 900 launches of 64 lanes, each 2 rows + xBC, dt and y at 819 GB/s
    least = 900 * 64 * costs_hybrid.lane_bytes(ssm, True) / 819e9
    assert share == pytest.approx(100 * least / 0.9)
    assert _load("ssm_step_roofline").read({**rec, "trace": {"ops": {}}}) \
        is None
    assert _load("serve.state_row_occupancy").read({}) is None


def test_span_readers_read_the_chain_engine():
    """The serving engine's span metrics listed for the chat cell find
    their spans when ``HybridChainEngine`` serves (no ``cb.project``:
    tokens are embedded inside the window)."""
    import jax
    import numpy as np
    from loops import serve_lm
    from reference import granite_hybrid as ref
    from repro.models.granite_hybrid import GraniteHybridVertex, HybridDims
    from repro.serve import HybridChainEngine
    from tracing import SpanWindow

    cfg = {**json.load(open(os.path.join(
        ROOT, "bench", "configs", "granite4_h_micro_l10.json"))),
        **SMALL_CFG}
    params = ref.init(jax.random.PRNGKey(3), cfg)
    fn = GraniteHybridVertex(HybridDims.from_hf(cfg), **cfg["vertex_args"])
    eng = HybridChainEngine(fn, params, num_rows=4, kv_tokens=8 * 48,
                            frontier_width=4)
    rng = np.random.default_rng(5)
    window = SpanWindow()
    for i, n in enumerate((7, 12, 3, 9, 5)):
        eng.submit(serve_lm._request(i, rng.integers(0, 512, n)))
    eng.run()
    window.stop()
    assert all(r.status == "ok" for r in eng.finished)
    rec = {"spans": window.spans(), "span_ticks": eng.ticks}
    names = {s.name for s in rec["spans"]}
    assert "cb.project" not in names and "cb.kv_alloc" in names
    for name in ("serve.host_ms_per_tick", "serve.device_wait_ms_per_tick"):
        assert _load(name).read(rec) > 0, name
