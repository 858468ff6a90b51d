"""The benchmark's traffic generator, work and bytes functions and trace
reduction, on the CPU. No TPU library is loaded."""

import math
import os

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import traffic
from bench import trace
from bench.work import calls, matmul, model, paged_attn

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEEPSEEK = traffic.json.load(open(os.path.join(tiny.ROOT, "bench", "configs",
                                               "deepseek-7b-d8.json")))
# granite-20b-code-base's widths (ibm-granite/granite-20b-code-base
# config.json) at 4 of 52 layers: multi-query attention, GELU FFN
GRANITE = {"hidden_size": 6144, "intermediate_size": 24576,
           "num_hidden_layers": 4, "num_attention_heads": 48,
           "num_key_value_heads": 1, "vocab_size": 49152,
           "hidden_act": "gelu"}
PEAK = tiny.PEAK


@pytest.mark.parametrize("mix_name", ["chat-steady", "resident"])
def test_generator_is_a_function_of_the_seed(mix_name):
    mix = (tiny.RESIDENT if mix_name == "resident"
           else traffic.load_mix(mix_name))
    a = traffic.generate(mix, 2**33 + 5, 30, 1000)
    b = traffic.generate(mix, 2**33 + 5, 30, 1000)
    c = traffic.generate(mix, 2**33 + 6, 30, 1000)
    key = lambda specs: [(s.prompt_len, s.max_new, s.due_s,
                          s.tokens.tobytes()) for s in specs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # every seed gets the same work, at the same times: only tokens differ
    work = lambda specs: [(s.prompt_len, s.max_new, s.due_s) for s in specs]
    assert work(a) == work(c)
    span = traffic.lead_s(mix) + 30
    assert all(0 <= s.due_s < span for s in a)
    assert all(1 <= s.tokens.min() and s.tokens.max() < 1000 for s in a)
    assert [len(s.tokens) for s in a] == [s.prompt_len for s in a]


def test_open_loop_gaps_are_a_permuted_poisson_multiset():
    mix = traffic.load_mix("chat-steady")
    a = traffic.generate(mix, 1, 40, 100)
    span = mix["lead_s"] + 40
    n = len(a)
    assert n == round(mix["rate_per_s"] * span)
    # the gaps are the exponential's stratified quantiles, scaled to the
    # span, less the one after the last arrival
    want = np.array([-math.log(1 - (i + 0.5) / n) for i in range(n)])
    want *= span / want.sum()
    got = np.diff([s.due_s for s in a])
    assert np.isin(np.round(got, 9), np.round(want, 9)).all()
    # in an order, not sorted
    assert not (np.all(np.diff(got) >= 0) or np.all(np.diff(got) <= 0))


def test_a_schedule_seed_fixes_the_order_for_every_seed():
    mix = traffic.load_mix("chat-steady")
    a = traffic.generate(mix, 1, 40, 100)
    b = traffic.generate(mix, 2**33 + 2, 40, 100)
    assert [(s.prompt_len, s.max_new, s.due_s) for s in a] == \
        [(s.prompt_len, s.max_new, s.due_s) for s in b]
    assert [s.tokens.tobytes() for s in a] != [s.tokens.tobytes() for s in b]
    c = traffic.generate(dict(mix, schedule_seed=1), 1, 40, 100)
    assert sorted(s.prompt_len for s in a) == sorted(s.prompt_len for s in c)
    assert [s.prompt_len for s in a] != [s.prompt_len for s in c]


def test_lead_in_arrivals_come_before_the_window():
    mix = traffic.load_mix("chat-steady")
    specs = traffic.generate(mix, 7, 51, 100)
    lead = [s for s in specs if s.due_s < mix["lead_s"]]
    in_window = [s for s in specs if s.due_s >= mix["lead_s"]]
    assert len(specs) == round(mix["rate_per_s"] * (mix["lead_s"] + 51))
    # about rate x seconds each side: the window's tail needs its requests
    assert abs(len(in_window) - mix["rate_per_s"] * 51) <= 6
    assert abs(len(lead) - mix["rate_per_s"] * mix["lead_s"]) <= 6


def test_lognormal_lengths_follow_the_mix():
    mix = traffic.load_mix("chat-steady")
    specs = traffic.generate(mix, 3, 50, 100)
    plens = [s.prompt_len for s in specs]
    assert min(plens) >= 32 and max(plens) <= 2048
    assert abs(np.median(plens) - 256) <= 16


def test_matmul_work_deepseek_ffn_decode_call():
    # 32 live slots through the SwiGLU gate: (32, 4096) @ (4096, 11008)
    m, k, n = 32, 4096, 11008
    assert matmul.ops(m, k, n) == 2 * 32 * 4096 * 11008 == 2885681152
    # codes 32*4096 + 4096*11008, scales 4*33, f32 out 4*32*11008
    assert matmul.hbm_bytes(m, k, n) == 131072 + 45088768 + 132 + 1409024
    t = matmul.least_s(m, k, n, PEAK)
    assert t == pytest.approx((131072 + 45088768 + 132 + 1409024) / 819e9)


def test_matmul_work_granite_prefill_projection():
    # a 1,000-token granite prompt through the 6144 -> 24576 GELU FFN
    m, k, n = 1000, 6144, 24576
    assert matmul.ops(m, k, n) == 301989888000
    assert matmul.least_s(m, k, n, PEAK) == pytest.approx(301989888000 / 393e12)


def test_paged_attention_work_hand_counts():
    # deepseek: 32 heads of 128 over 32 KV heads; two slots at 2048, 2049
    ops = paged_attn.ops([2048, 2049], 32, 128)
    assert ops == 4 * 128 * 32 * (2048 + 2049)
    b = paged_attn.hbm_bytes([2048], 32, 32, 128)
    assert b == 2 * 32 * 2048 * 132 + (32 * 128 + 4 * 32) + 4 * 32 * 128
    # granite: 48 query heads share one KV head of 128
    b = paged_attn.hbm_bytes([1000], 48, 1, 128)
    assert b == 2 * 1 * 1000 * 132 + (48 * 128 + 4) + 4 * 48 * 128


def test_model_ops_per_token():
    p = model.matmul_params(DEEPSEEK)
    assert p == 8 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 4096 * 102400
    assert model.token_ops(DEEPSEEK, 100) == 2.0 * p + 4 * 128 * 100 * 32 * 8
    g = model.matmul_params(GRANITE)
    assert g == 4 * (2 * 6144 * 6144 + 2 * 6144 * 128 + 2 * 6144 * 24576) \
        + 6144 * 49152
    # a prompt: causal attention over 1..n keys, the head once
    n = 10
    want = (2.0 * (g - 6144 * 49152) * n
            + sum(4 * 128 * (t + 1) * 48 * 4 for t in range(n))
            + 2.0 * 6144 * 49152)
    assert model.prefill_ops(GRANITE, n) == pytest.approx(want)


def test_call_lists_cover_the_layer():
    dec = calls.decode_matmuls(DEEPSEEK, 5)
    assert len(dec) == 8 * 7 + 1 and dec[-1] == (5, 4096, 102400)
    pre = calls.prefill_matmuls(GRANITE, 700, 1024)
    # 6 projections + 1 chunk x 1 KV head x (scores, values) per layer
    assert len(pre) == 4 * (6 + 2) + 1
    assert (700 * 48, 128, 1024) in pre and pre[-1] == (1, 6144, 49152)


def test_trace_reduction_on_a_recorded_chip_trace():
    # a slice of a TPU v5e trace: deepseek-7b at 2 layers, 8 slots; two
    # admissions (prefill + adopt) and two decode steps
    ev = trace.load_events(os.path.join(DATA, "chip_trace_slice.json.gz"))
    red = trace.reduce(ev)
    assert red.program_n == {"other": 38, "prefill": 2, "adopt": 2,
                             "decode": 2}
    pf = sum(e - s for n, s, e in ev["modules"] if n.startswith("jit__pf("))
    assert red.program_s["prefill"] == pytest.approx(pf * 1e-9)
    assert red.program_s["prefill"] == pytest.approx(0.115430212)
    assert red.program_s["decode"] == pytest.approx(0.053343997)
    assert red.kernel_s[("matmul", "prefill")] == pytest.approx(0.09990725)
    assert red.kernel_s[("matmul", "decode")] == pytest.approx(0.039579854)
    assert red.kernel_s[("paged_attn", "decode")] == pytest.approx(0.001448515)
    # busy is a union: no more than the span, no less than the programs
    span = (max(e for _, _, e in ev["ops"]) - min(s for _, s, _ in ev["ops"]))
    assert sum(red.program_s.values()) <= red.busy_s + 1e-9
    assert red.busy_s <= span * 1e-9
    assert red.device_ops[0][0] == "mgs_matmul_exact_fused_pallas"
    assert len(red.idle_gaps) == 10 and all(g > 0 for _, g in red.idle_gaps)
    trace.check(red, prefills=2, decode_rounds=2)


def test_trace_reduction_fails_loudly_on_a_missing_name():
    ev = trace.load_events(os.path.join(DATA, "chip_trace_slice.json.gz"))
    ev = dict(ev, ops=[o for o in ev["ops"]
                       if "mgs_paged_flash_attention" not in o[0]])
    red = trace.reduce(ev)
    with pytest.raises(ValueError, match="paged attention"):
        trace.check(red, prefills=2, decode_rounds=2)
    ev = dict(ev, modules=[m for m in ev["modules"]
                           if not m[0].startswith("jit__pf(")])
    with pytest.raises(ValueError, match="prefill program"):
        trace.check(trace.reduce(ev), prefills=1, decode_rounds=0)


def test_short_names():
    assert trace.short_name(
        "%mgs_matmul_exact_fused_pallas.92 = f32[128,102400] custom-call(x)"
    ) == "mgs_matmul_exact_fused_pallas"
    assert trace.short_name("%vmap_jit_mgs_matmul_exact_fused_pallas__.31 = "
                            ) == "vmap_jit_mgs_matmul_exact_fused_pallas__"
    assert trace.program_of("jit__dp(1093)") == "decode"
    assert trace.program_of("jit_squeeze(17)") == "other"


def test_nearest_rank_percentile():
    from bench.cell import nearest_rank
    v = list(range(1, 101))
    assert nearest_rank(v, 0.90) == 90 and nearest_rank(v, 0.99) == 99
    assert math.isnan(nearest_rank([], 0.5))


def _two_request_run():
    """A window of two requests: the second one's admission stretches the
    first one's gap to 3 s in the round that starts at 3.6 s."""
    import types
    from bench.cell import Run, Tokens
    reqs = []
    for stamps in ([1.5, 2.5, 3.5, 6.5], [6.5, 7.5]):
        out = Tokens()
        out.extend(range(len(stamps)))
        out.t = stamps
        reqs.append(types.SimpleNamespace(out_tokens=out, done=False))
    specs = [traffic.Spec(100, 4, 0.5, np.zeros(100, np.int32)),
             traffic.Spec(200, 2, 3.2, np.zeros(200, np.int32))]
    return Run(specs=specs, reqs=reqs, buckets=[128, 256],
               rounds=[1.0, 2.0, 3.0, 3.6, 6.6, 7.0], t0=0.0, t_start=0.0,
               t_end=20.0)


def test_longest_rounds_name_what_they_admitted():
    from bench.cell import longest_rounds
    top = longest_rounds(_two_request_run(), [0, 1])
    assert top[0] == [3000.0, 1, [256]]
    assert [r[0] for r in top] == [3000.0, 1000.0, 1000.0, 1000.0]


def test_itl_tail_reader_reads_the_host_gaps():
    import types
    from bench.metrics import itl_tail_p99_ms
    mix = {"end": "first_token", "drain_cap_s": 0.0}
    run = _two_request_run()
    assert itl_tail_p99_ms.read(types.SimpleNamespace(run=run, mix=mix)) == 3000.0
    # a profiler started at 4 s stretched the 3-s gap: it is left out
    run.trace_span = (4.0, 7.2)
    assert itl_tail_p99_ms.read(types.SimpleNamespace(run=run, mix=mix)) == 1000.0
    for r in run.reqs:
        r.out_tokens.t = r.out_tokens.t[:1]
    assert itl_tail_p99_ms.read(types.SimpleNamespace(run=run, mix=mix)) is None
