"""CPU rehearsal of the serve cell through the harness's own functions, at
a reduced size with the kernels interpreted: the last line parses to the
result line's keys, the traced run reads its per-layer metrics, and the
control in the program's place, like each fault planted in the timed
path, turns ``correct`` false.  The command itself refuses to run without a TPU, so
these call ``run.run`` with the CPU's devices."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from harness import common

BENCH = str(common.BENCH)
ROOT = str(common.ROOT)
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def reduced_manifest(d) -> str:
    """BENCHMARK.json with the configuration cut to a CPU's size (every
    width reduced, two layers, few experts) and short traffic."""
    os.makedirs(f"{d}/bench/configs", exist_ok=True)
    os.makedirs(f"{d}/bench/mixes", exist_ok=True)
    cfgs = f"{BENCH}/configs"
    g = common.load_json(f"{cfgs}/granite-moe-1b-a400m.json")
    g.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=32, num_local_experts=8,
             num_experts_per_tok=2, vocab_size=256)
    g["program"]["set"]["interpret_kernels"] = True
    g["serve"].update(slots=4, max_len=128,
                      kernels={"prefill": [], "decode": []})
    # at this size sound runs read gaps of 0 to 0.011 and the fp8
    # control 0.035 to 0.098 (seeds 11-15 and 2**33 + 7)
    g["limits"].update(min_sampled_tokens=10, served_gap_z_max=0.025)
    for key in ("reference", "work"):
        g[key] = f"{cfgs}/{g[key]}"
    g["program"]["weights"] = f"{cfgs}/{g['program']['weights']}"
    with open(f"{d}/bench/configs/{g['name']}.json", "w") as f:
        json.dump(g, f)
    chat = common.load_json(f"{BENCH}/mixes/chat-steady.json")
    chat.update(arrivals={"process": "poisson", "rate_per_s": 10.0},
                in_flight_at_open=3,
                prompt_tokens={"dist": "lognormal", "median": 12,
                               "sigma": 1.0, "min": 4, "max": 40},
                output_tokens={"dist": "lognormal", "median": 6,
                               "sigma": 0.8, "min": 2, "max": 16})
    with open(f"{d}/bench/mixes/chat-steady.json", "w") as f:
        json.dump(chat, f)
    with open(f"{d}/BENCHMARK.json", "w") as f:
        json.dump(common.load_json(f"{ROOT}/BENCHMARK.json"), f)
    return f"{d}/BENCHMARK.json"


RUN = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax
    from harness import common
    common.peaks_for = lambda kind: {{"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9}}
    import run
    args = run.parse({argv!r})
    m = common.Manifest(args.manifest)
    devs = jax.devices()[:m.workload(args.workload)["chips"]]
    print(run.run(args, devs, m, common.CompileWatch()))
""")


def rehearse(tmp_path, workload, *extra):
    """One run of ``workload`` on the CPU in a child process; the parsed
    last line."""
    argv = ["--workload", workload, "--seed", str(2 ** 33 + 7),
            "--seconds", "2", "--manifest", reduced_manifest(tmp_path),
            *extra]
    code = RUN.format(bench=BENCH, src=f"{ROOT}/src", argv=argv)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, cwd=str(tmp_path), env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert KEYS <= set(line)
    assert list(line)[-1] == "checks"
    return line


def test_serve_cell_rehearses_and_reports_its_end_to_end_metrics(tmp_path):
    line = rehearse(tmp_path, "granite-chat-steady", "--trace", "0")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ttft_p90_ms", "itl_p99_ms",
                                    "serve_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    c = line["checks"]
    assert c["served_gap_z_max"]["value"] <= c["served_gap_z_max"]["limit"]
    assert c["sampled_tokens"]["value"] >= c["sampled_tokens"]["limit"]


def test_serve_control_in_the_programs_place_turns_correct_false(tmp_path):
    line = rehearse(tmp_path, "granite-chat-steady", "--control", "1")
    assert line["correct"] is False
    c = line["checks"]
    # the fp8 control is judged on the same prompts and tokens, alone
    assert "served_gap_z_max" not in c
    assert c["control_gap_z_max"]["value"] > c["control_gap_z_max"]["limit"]


def test_serve_cell_traced_run_reads_host_side_layer_metrics(tmp_path):
    line = rehearse(tmp_path, "granite-chat-steady", "--trace", "1")
    assert line["correct"] is True
    # no device trace on a CPU: the device metrics are left out, the
    # host's are read
    assert "queue_wait_p95_ms" in line["metrics"]
    assert "backend_compile_s" in line["metrics"]
    assert "mfu.decode" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", ["token", "stale_cache"])
def test_serve_faults_turn_correct_false(tmp_path, fault):
    line = rehearse(tmp_path, "granite-chat-steady", "--fault", fault)
    assert line["correct"] is False


def test_command_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload",
         "granite-chat-steady", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr

