"""Peak table and the required-work functions, against hand counts; padding
and re-forwards are not counted."""

import pytest

from bench import harness
from bench.metrics import _count as c

M = dict(num_layers=1, num_heads=1, num_kv_heads=1, head_dim=2, d_model=2, d_ff=4,
         vocab_size=3, value_head=[2])


def reader(name):
    return harness.reader(name)


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]).read), m["name"]
    # A family suffix falls back to the shared reader; an unknown name is an error.
    assert harness.reader("learner.device_ms.lm").__file__.endswith("learner.device_ms.py")
    with pytest.raises(SystemExit):
        harness.reader("no_such_metric")


def test_peak_table_is_keyed_by_device_kind_and_refuses_others():
    assert harness.peak_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        harness.peak_for("TPU v9 imaginary")


def test_causal_attention_hand_count():
    # length 3: 6 query-key pairs; QK^T and PV each 6 x 2 x D FLOPs.
    flops, byts = c.causal_attention(M, [3])
    assert flops == 6 * 2 * 2 + 6 * 2 * 2
    assert byts == 4 * 3 * 2 * 4  # q, k, v, out: 3 rows of D=2 floats each


def test_decode_attention_hand_count():
    m = dict(M, num_heads=2, head_dim=4)
    flops, byts = c.decode_attention(m, [5])
    assert flops == 2 * (2 * 4 * 5) * 2  # QK and PV, 2 heads, 5 positions
    assert byts == (2 * 1 * 4 * 5 + 2 * 2 * 4) * 4  # valid K and V prefix, q, out


def test_lm_matmul_params_hand_count():
    # attention 4 x (2x2), MLP 3 x (2x4), head 2x3, value 2x2 + 2x1
    assert c.lm_matmul_params(M) == 16 + 24 + 6 + 6


def _facts(ctx, epochs=1, seconds=1.0):
    import numpy as np

    return {
        "model": M, "traffic": {"sgd_epochs": epochs, "ctx": ctx}, "chips": 1,
        "peak": harness.peak_for("TPU v5 lite"), "units": 4, "window_s": 2.0,
        "rows": [{"length": np.array([3, 4, 5, 6]), "t": np.array([0, 1, 2, 3])}],
        "trace": {"ops": {"k": {"s": seconds, "calls": 1, "opcode": "custom-call",
                                "arrays": [], "module": "", "operands": [], "out_space": 0,
                                "text": "_flash_kernel _decode_kernel"}}},
    }


def test_padding_and_reforwards_are_not_counted():
    # The window length (ctx) is padding: it changes no required work.
    for name in ("flash_attention_roofline", "decode_attention_roofline", "step_mfu.lm"):
        assert reader(name).read(_facts(32)) == reader(name).read(_facts(4096))
    # Per trained token: one generation forward, plus 3x per epoch.
    f = _facts(32)
    per_token = sum(c.lm_token_forward_flops(M, [3, 4, 5, 6])) / 4 * (1 + 3)
    assert reader("step_mfu.lm").read(f) == pytest.approx(100 * per_token * 4 / 2.0 / 197e12)


def test_flash_roofline_counts_learner_bootstrap_and_prefill():
    f = _facts(32, epochs=2)
    L = [3, 4, 5, 6]
    fl = 2 * c.causal_attention(M, L)[0] + c.causal_attention(M, [x + 1 for x in L])[0] \
        + c.causal_attention(M, [3])[0]
    by = 2 * c.causal_attention(M, L)[1] + c.causal_attention(M, [x + 1 for x in L])[1] \
        + c.causal_attention(M, [3])[1]
    want = 100 * max(fl / 197e12, by / 819e9)
    assert reader("flash_attention_roofline").read(f) == pytest.approx(want)


def test_absent_kernel_reads_nothing():
    f = _facts(32)
    f["trace"] = {"ops": {}}
    assert reader("flash_attention_roofline").read(f) is None
    assert reader("decode_attention_roofline").read(f) is None


def test_decode_counts_valid_k_and_v_wherever_kept_and_only_in_the_rollout():
    import numpy as np

    from bench import trace as tr

    m = dict(M, num_heads=32, head_dim=64, num_kv_heads=32)
    # V, the query and the output sit in on-chip VMEM (S(1)): still counted.
    name = ("%closed_call.32 = f32[8,32,64]{2,1,0:T(8,128)S(1)} custom-call("
            "f32[8,32,64]{2,1,0:T(8,128)S(1)} %q, f32[8,512,2048]{2,1,0:T(8,128)} %k, "
            "f32[8,512,2048]{2,1,0:T(8,128)S(1)} %v, s32[8,1,512]{2,1,0:T(1,128)S(1)} %m), "
            'custom_call_target="tpu_custom_call"')
    p = tr.parse_op(name)
    assert [sp for _, _, sp in p["operands"]] == [1, 0, 1, 1] and p["out_space"] == 1

    def rec(module, s):
        return dict(s=s, calls=2, module=module, opcode=p["opcode"], arrays=p["arrays"],
                    operands=p["operands"], out_space=p["out_space"], text=name)

    L = np.array([10, 20])
    ops = {"d": rec("jit__vrollout", 1e-3), "other": rec("jit__learn", 5e-3)}
    facts = {"model": m, "traffic": {}, "peak": harness.peak_for("TPU v5 lite"),
             "rows": [{"length": L, "t": np.array([1, 1])}], "trace": {"ops": ops}}
    _, byts = c.decode_attention(m, L)
    # K and V prefixes (30 positions of KV x D floats each), q and out per row.
    assert byts == (2 * 30 * 32 * 64 + 2 * 2 * 32 * 64) * 4
    assert reader("decode_attention_roofline").read(facts) == pytest.approx(
        100 * (byts / 819e9) / 1e-3)
