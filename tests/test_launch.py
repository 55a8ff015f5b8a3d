"""Launcher smoke coverage: `python -m repro.launch.{train,serve}` end to
end in a subprocess (the exact user entrypoints — argparse, Trainer /
scheduler wiring, --json-out), asserting the JSON outputs are
well-formed."""
import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_module(args, timeout=540, module="repro.launch.train"):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-m", module] + args,
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"launcher failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


def test_train_cli_smoke_json_history(tmp_path):
    """8 reduced steps with periodic BLEU eval; --batch/--seq shrunk so the
    chunk executables compile quickly. Asserts the --json-out schema the
    benchmarks consume."""
    out_json = str(tmp_path / "hist.json")
    # traced_cond -> one executable per chunk LENGTH (host_cond would also
    # specialize on the decision, doubling compile work — covered by
    # tests/test_trainer.py at tiny scale instead)
    stdout = run_module(["--reduced", "--steps", "8", "--eval-every", "4",
                         "--json-out", out_json,
                         "--batch", "4", "--seq", "16", "--chunk", "4",
                         "--strategy", "traced_cond",
                         "--microbatches", "2", "--schedule", "cosine"])
    with open(out_json) as f:
        data = json.load(f)
    assert data["arch"]
    assert data["gd"] is not None          # zcode-m3 carries a gd config
    hist = data["history"]
    assert hist, stdout
    steps = [r["step"] for r in hist]
    assert steps == sorted(steps)
    assert steps[-1] == 7
    for rec in hist:
        for k in ("loss", "acc", "lr", "tok_s", "time_s"):
            assert k in rec and np.isfinite(rec[k]), (rec, k)
        assert rec["tok_s"] > 0
    # --schedule cosine + warmup: lr must actually move between records
    lrs = {r["lr"] for r in hist}
    assert len(lrs) > 1, hist
    # eval steps (0, 4, last) carry a BLEU value
    bleu_steps = {r["step"] for r in hist if "bleu" in r}
    assert {0, 4, 7} <= bleu_steps, hist
    assert all(np.isfinite(r["bleu"]) for r in hist if "bleu" in r)
    # stdout mirrors the history as JSON lines
    assert any('"step": 7' in l for l in stdout.splitlines())


def test_serve_cli_trace_smoke_json(tmp_path):
    """Continuous-batching serving loop end to end (DESIGN.md §9):
    synthetic Poisson trace through the scheduler, --json-out schema the
    benchmarks consume, every request admitted AND finished."""
    out_json = str(tmp_path / "serve.json")
    run_module(["--arch", "yi-6b", "--reduced", "--trace", "6",
                "--rate", "500", "--slots", "2", "--max-new", "6",
                "--buckets", "8", "--eos", "-1",
                "--json-out", out_json], module="repro.launch.serve")
    with open(out_json) as f:
        rec = json.load(f)
    assert rec["mode"] == "continuous"
    assert rec["n_requests"] == 6
    assert rec["scheduler"]["admitted"] == 6
    assert rec["scheduler"]["finished"] == 6
    assert rec["scheduler"]["max_concurrent"] <= 2
    # eos disabled: every request runs to its sampled budget in [2, 6]
    assert 6 * 2 <= rec["n_tokens"] <= 6 * 6
    assert rec["tok_s"] > 0
    for p in ("50", "90", "99"):
        assert np.isfinite(rec["ttft_s"][p])
        assert np.isfinite(rec["per_token_latency_s"][p])
    # mid-flight admission: 6 requests through 2 slots -> slots reused
    assert rec["scheduler"]["slot_reuse"] >= 4


def test_serve_cli_trace_comm_accounting(tmp_path):
    """MoE arch: the trace record prices every executed tick with the
    expert exchange's bytes model (DESIGN.md §10) at --comm-ep: two
    all-to-alls per MoE layer, each moving 7/8 of its buffer at ep=8."""
    from repro.configs import get_config, reduced
    from repro.training.steps import n_moe_layers
    out_json = str(tmp_path / "serve_comm.json")
    stdout = run_module(["--arch", "dbrx-132b", "--reduced", "--trace", "4",
                         "--rate", "500", "--slots", "2", "--max-new", "4",
                         "--buckets", "8", "--eos", "-1", "--comm-ep", "8",
                         "--json-out", out_json],
                        module="repro.launch.serve")
    with open(out_json) as f:
        rec = json.load(f)
    comm = rec["comm"]
    assert comm["ep_model"] == 8
    assert comm["wire_bytes_total"] > 0
    sched = rec["scheduler"]
    assert comm["n_ticks"] == sched["prefill_calls"] + sched["decode_steps"]
    for p in ("50", "90", "99"):
        assert np.isfinite(comm["wire_bytes_per_tick"][p])
    # dense pricing by hand: a tick of <= 8 tokens has one token per
    # shard (capacity 1), a two-request prefill (16 tokens) capacity 2
    cfg = reduced(get_config("dbrx-132b"))
    row = cfg.d_model * np.dtype(cfg.dtype).itemsize
    unit = 2 * cfg.moe.n_experts * row * 7 / 8 * n_moe_layers(cfg)
    slots = comm["wire_bytes_total"] / unit
    assert slots == int(slots), slots
    assert (comm["n_ticks"] <= slots
            <= comm["n_ticks"] + sched["prefill_calls"]), slots
    assert "comm[ep=8]" in stdout


def test_dryrun_import_merges_caller_xla_flags():
    """Importing the dry-run adds its simulated-device count to the
    caller's XLA_FLAGS instead of replacing them, and a caller-set device
    count wins."""
    code = ("import os; import repro.launch.dryrun; "
            "print(os.environ['XLA_FLAGS'])")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    for given, want in (
            ("--xla_cpu_enable_fast_math=false",
             "--xla_cpu_enable_fast_math=false "
             "--xla_force_host_platform_device_count=512"),
            ("--xla_force_host_platform_device_count=8",
             "--xla_force_host_platform_device_count=8")):
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=dict(env, XLA_FLAGS=given),
                           timeout=120)
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == want
