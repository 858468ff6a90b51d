"""The benchmark harness on the CPU: its refusals, a whole run of a tiny
cell with the chip check skipped, the int4 control and the faults that
``correct`` must catch, and the reference against the program's
unquantized float path."""

import dataclasses
import json

import numpy as np
import pytest

import tiny
from bench import cell, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write_root(str(tmp_path_factory.mktemp("benchroot")))


def test_refuses_without_a_tpu(capsys, monkeypatch):
    # keep the persistent compile cache off in the test process
    monkeypatch.setattr(run, "configure_jax", lambda: None)
    rc = run.main(["--workload", "ds7b-chat-steady", "--seed", "1",
                   "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "no TPU" in err


def test_refuses_an_unknown_device_kind(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(cell.BenchError, match="no peaks"):
        cell.require_chip(1, cell.load_peaks())
    assert "TPU v5 lite" in cell.load_peaks()


def test_refuses_too_few_chips(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(cell.BenchError, match="needs 4"):
        cell.require_chip(4, cell.load_peaks())


@pytest.mark.parametrize("workload", ["tiny-chat", "tiny-resident"])
def test_a_whole_run_is_correct(root, monkeypatch, workload):
    tiny.patch_cpu(monkeypatch)
    r = run.run_cell(tiny.args(workload, 2**33 + 17), root=root)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "compared"
    c = r["compared"]
    assert c["max_logit_rel_err"]["value"] <= c["max_logit_rel_err"]["limit"]
    assert c["sampled_tokens"] > 0
    m = r["metrics"]
    assert {"setup_s", "itl_p95_ms", "ttft_p75_s"} <= set(m)
    assert all(np.isfinite(v["value"]) for v in m.values())
    json.dumps(r, allow_nan=False)


def test_the_window_opens_after_the_lead_in(root, monkeypatch):
    tiny.patch_cpu(monkeypatch)
    from bench import correct, traffic
    seen = {}
    drive = cell.drive

    def spy(engine, specs, mix, seconds, **kw):
        seen["run"] = r = drive(engine, specs, mix, seconds, **kw)
        seen["mix"] = mix
        return r

    monkeypatch.setattr(cell, "drive", spy)
    res = run.run_cell(tiny.args("tiny-chat", 2**33 + 21, seconds=3.0),
                       root=root)
    r, lead = seen["run"], traffic.lead_s(seen["mix"])
    assert lead > 0 and r.t_start == r.t0 + lead
    due = cell.due_times(r)
    win = cell.window_requests(r)
    assert win == [i for i, s in enumerate(r.specs)
                   if lead <= s.due_s < lead + 3.0]
    assert res["attempted"] == len(win) > 0
    # the sample is drawn from the lead-in's requests, served in the window
    assert r.sample == correct.pre_sample(r.specs, 2**33 + 21, lead)
    assert all(due[i] < r.t_start for i in r.sample)
    # occupancy is read at every round inside the window, none before it
    assert r.occupancy and all(o[0] >= r.t_start for o in r.occupancy)
    assert all(0 <= o[1] <= 4 and o[2] <= r.kv_blocks for o in r.occupancy)


def test_int4_control_is_not_correct(root, monkeypatch):
    tiny.patch_cpu(monkeypatch)
    r = run.run_cell(tiny.args("tiny-chat", 2**33 + 18), root=root,
                     control=True)
    c = r["compared"]
    assert c["max_logit_rel_err"]["value"] <= c["max_logit_rel_err"]["limit"]
    assert c["control_max_logit_rel_err"] > c["max_logit_rel_err"]["limit"]


def _faulty(monkeypatch, fault):
    """Build engines whose decode step carries ``fault``."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    real = serve.make_engine

    def make(*a, **kw):
        e = real(*a, **kw)
        step = e._decode_paged

        def broken(p, t, c, cs=None):
            if fault == "state_unchanged":
                keep = jax.tree.map(jnp.copy, c)
                logits, _ = step(p, t, c, cs)
                return logits, keep
            logits, c = step(p, t, c, cs)
            if fault == "token_altered":
                logits = logits.at[:, 7].add(1e3)
            elif fault == "half_batch":
                h = logits.shape[0] // 2
                logits = jnp.concatenate([logits[:h], logits[:h]])
            return logits, c

        broken._cache_size = step._cache_size
        e._decode_paged = broken
        return e

    monkeypatch.setattr(serve, "make_engine", make)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    tiny.patch_cpu(monkeypatch)
    _faulty(monkeypatch, fault)
    r = run.run_cell(tiny.args("tiny-resident", 2**33 + 19), root=root)
    c = r["compared"]
    assert r["correct"] is False
    assert c["max_logit_rel_err"]["value"] > c["max_logit_rel_err"]["limit"]


def test_a_compile_in_the_window_fails_the_run(root, monkeypatch):
    tiny.patch_cpu(monkeypatch)
    from repro.launch.serve import ContinuousBatchingEngine
    warm = ContinuousBatchingEngine.warmup
    # warm-up requests done at their first token: the decode program then
    # compiles in the window
    monkeypatch.setattr(ContinuousBatchingEngine, "warmup",
                        lambda self, b, max_new=1, seed=0:
                        warm(self, b, max_new=1, seed=seed))
    with pytest.raises(cell.BenchError, match="inside the window"):
        run.run_cell(tiny.args("tiny-chat", 2**33 + 20), root=root)


def test_reference_matches_the_programs_float_path():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import forward, init_params
    from bench.reference import dense
    for act, kv in (("silu", 4), ("gelu", 1)):
        cfg = dataclasses.replace(
            get_config("deepseek-7b"), n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=kv, head_dim=16, d_ff=96, vocab=128, attn_chunk=0,
            act=act, compute_dtype="float32", remat="none")
        c = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=kv,
                 vocab_size=128, hidden_act=act, rms_norm_eps=1e-6,
                 rope_theta=10000.0)
        seed = 1234567
        params, _ = init_params(cfg, jax.random.PRNGKey(seed))
        w = dense.layer_weights(c, seed, 1)
        for name, got in (("wq", params["layers"]["attn"]["wq"][1]),
                          ("wd", params["layers"]["ffn"]["wd"][1])):
            assert np.array_equal(np.asarray(got), np.asarray(w[name]))
        assert np.array_equal(np.asarray(params["embed"]),
                              np.asarray(dense.embed_table(c, seed)))
        toks = np.random.default_rng(0).integers(0, 128, (2, 512))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(forward(params, cfg,
                                      {"tokens": jnp.asarray(toks)})[0])
        got = dense.forward_logits(c, seed, toks)
        # both float32 at highest precision; they differ in reduction
        # order only (RMSNorm's pairwise row sum, the chunked softmax)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
