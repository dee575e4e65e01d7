"""Each runner rehearsed end to end at a tiny size on the CPU mesh, through
the command's own ``main`` — pointed at a temporary checkout whose
configurations are tiny (they live here, not in ``benchmark/configs/``) —
and the command's refusals. A rehearsal proves control flow and counts;
its seconds mean nothing and no device metric is read from it."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest as mf
from benchmark import run as bench_run

REPO = mf.CHECKOUT

TINY_NEOX = {
    "block": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "vocab_size": 256,
    "max_position_embeddings": 256, "rotary_pct": 0.25,
    "use_parallel_residual": True, "tie_word_embeddings": False,
    "transformer_config": {
        "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_layers": 2, "num_heads": 4, "max_seq_len": 256,
        "norm": "layernorm", "norm_eps": 1e-5, "activation": "gelu_exact",
        "position": "rope", "rope_pct": 0.25, "rope_theta": 10000,
        "parallel_residual": True, "tie_embeddings": False,
        "use_bias": True, "dtype": "float32"},
    "engine": {"kv_block_size": 16, "kv_blocks": 128,
               "max_ragged_sequence_count": 4, "max_chunk_tokens": 32,
               "max_ragged_batch_size": 96},
    "check": {"requests": 2, "decode_steps": 2, "max_prompt_tokens": 256,
              "tolerance": 1e-4, "rms_tolerance": 1e-4},
}
TINY_MISTRAL = {
    "block": "dense",
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "max_position_embeddings": 256, "sliding_window": 64,
    "tie_word_embeddings": False,
    "transformer_config": {
        "vocab_size": 256, "hidden_size": 64, "intermediate_size": 160,
        "num_layers": 2, "num_heads": 4, "num_kv_heads": 2,
        "max_seq_len": 128, "sliding_window": 64, "norm": "rmsnorm",
        "norm_eps": 1e-5, "activation": "silu", "position": "rope",
        "rope_pct": 1.0, "rope_theta": 10000.0, "parallel_residual": False,
        "tie_embeddings": False, "use_bias": False, "dtype": "float32"},
    "engine": dict(TINY_NEOX["engine"]),
    "check": {"requests": 2, "decode_steps": 2, "max_prompt_tokens": 128,
              "tolerance": 1e-4, "rms_tolerance": 1e-4, "loss_tolerance": 0.01},
}
LENGTHS = {"generator": "stratified",
           "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 8, "max": 90},
           "output_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 14},
           "schedule_seed": 0, "preroll_s": 0.5, "drain_s": 30}
TRAFFIC = {
    "chat": dict(LENGTHS, loop="open"),
    "batch": dict(LENGTHS, loop="closed", clients=4),
    "zero3": {"generator": "token_batches", "sequence_tokens": 64,
              "sequences_per_chip": 1, "distinct_batches": 2},
}


@pytest.fixture()
def checkout(tmp_path, monkeypatch):
    """A checkout with the real harness, readers, generators and manifest,
    and tiny configurations and traffic mixes under the real names."""
    root = str(tmp_path)
    bench = os.path.join(root, "benchmark")
    for sub in ("blocks", "end_to_end", "layer_metrics", "workloads"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(bench, sub))
    shutil.copytree(os.path.join(REPO, "benchmark", "traffic"),
                    os.path.join(bench, "traffic"),
                    ignore=shutil.ignore_patterns("*.json", "__pycache__"))
    os.makedirs(os.path.join(root, "tests", "benchmark"))
    os.makedirs(os.path.join(root, "deepspeed_tpu"))     # "the program"
    manifest = mf.load()
    write = lambda rel, body: _write(os.path.join(root, rel), body)
    write("benchmark/configs/pythia-1.4b.json", TINY_NEOX)
    write("benchmark/configs/mistral-7b.json", TINY_MISTRAL)
    for name, body in TRAFFIC.items():
        write(f"benchmark/traffic/{name}.json", body)
    write("benchmark/traffic/doc.json", TRAFFIC["chat"])
    for cell in ("pythia-1.4b.chat", "pythia-1.4b.doc"):
        path = os.path.join(bench, "workloads", cell + ".json")
        write(path, dict(_read(path), rate_rps=10.0, trace_s=1.0))
    write("BENCHMARK.json", manifest)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(root, ".jax_cache"))
    return root


def _write(path, body):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(body, f)


def _read(path):
    with open(path) as f:
        return json.load(f)


def rehearse(root, capsys, cell, trace, seconds="1.5", seed="5"):
    capsys.readouterr()
    rc = bench_run.main(["--workload", cell, "--seed", seed, "--seconds",
                         seconds, "--trace", str(trace)], root=root,
                        platform="cpu")
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), json.loads(lines[-2])["extra"]


def check_line(line, manifest, cell, group):
    assert set(line) <= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"}
    # each number compared beside its limit, under the line's last key
    assert list(line)[-1] == "checks" and "compiles_in_window" in line["checks"]
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    assert line["device"]["platform"] == "cpu"      # says where it ran
    wanted = {m["name"]: m for m in mf.metrics_for(manifest, group, cell)}
    assert set(line["metrics"]) <= set(wanted)
    for name, m in line["metrics"].items():
        assert m["unit"] == wanted[name]["unit"]
        assert m["value"] == m["value"] and m["value"] != 0


def test_open_loop_cell_rehearsed(checkout, capsys):
    line, extra = rehearse(checkout, capsys, "pythia-1.4b.doc", 0)
    assert line["correct"], extra["why_not"]
    assert line["attempted"] >= 8 and line["failed"] == 0
    check_line(line, mf.load(checkout), "pythia-1.4b.doc", "end_to_end")
    assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    counters = extra["counters"]
    assert counters["compiles_in_window"] == 0
    assert counters["logits_check"]["max_rel_err"] < 1e-4
    # 3 sequence buckets x 6 chunk buckets, then each count 1..4
    assert counters["warm_up_calls"] == 3 * 6 + 4


def test_closed_loop_cell_rehearsed_and_traced(checkout, capsys):
    line, extra = rehearse(checkout, capsys, "mistral-7b.batch", 1)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0
    check_line(line, mf.load(checkout), "mistral-7b.batch", "per_layer")
    # host spans and counters are read; device-trace metrics are not,
    # off the chip, and neither are busy_s / breakdown
    assert {"sat_batch_seqs_mean", "sat_pad_ratio",
            "sat_kv_blocks_peak_share"} <= set(line["metrics"])
    assert not {"sat_fwd_decode_dev_ms", "sat_host_step_share",
                "sat_paged_attn_roofline"} & set(line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert 1 <= line["metrics"]["sat_batch_seqs_mean"]["value"] <= 4
    assert line["metrics"]["sat_pad_ratio"]["value"] >= 1


def test_train_cell_rehearsed_on_four_devices(checkout, capsys):
    line, extra = rehearse(checkout, capsys, "mistral-7b.zero3", 0)
    assert line["correct"], extra["why_not"]
    assert line["device"]["count"] == 8 and line["attempted"] >= 2
    check_line(line, mf.load(checkout), "mistral-7b.zero3", "end_to_end")
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    check = extra["counters"]["loss_check"]
    assert check["rel_err"] < 0.01
    assert extra["counters"]["last_losses"][-1] < \
        extra["counters"]["first_losses"][0]


def add_cell(checkout, traffic_name):
    """A later PR's entries for a cell ``pythia-1.4b.<traffic_name>`` that
    reports what the chat cell reports."""
    cell = "pythia-1.4b." + traffic_name
    _write(os.path.join(checkout, f"benchmark/workloads/{cell}.json"),
           {"runner": "serve", "rate_rps": 8.0})
    manifest = mf.load(checkout)
    manifest["workloads"].append(
        {"name": cell, "config": "pythia-1.4b", "traffic": traffic_name,
         "chips": 1, "why": "a later PR's cell"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "pythia-1.4b.chat" in m.get("workloads", []):
            m["workloads"].append(cell)
    _write(os.path.join(checkout, "BENCHMARK.json"), manifest)
    mf.validate(manifest, checkout)
    return cell


def test_a_cell_added_by_files_alone_runs(checkout, capsys):
    """A later PR's cell: a traffic mix (other lengths, another sample of
    the schedule), the cell's file, and entries — no code."""
    _write(os.path.join(checkout, "benchmark/traffic/short.json"),
           dict(TRAFFIC["chat"], schedule_seed=3,
                prompt_tokens={"median": 12, "sigma": 0.3, "min": 4,
                               "max": 24}))
    cell = add_cell(checkout, "short")
    line, extra = rehearse(checkout, capsys, cell, 0)
    assert line["correct"], extra["why_not"]
    assert set(line["metrics"]) == {"tpot_p90_ms", "setup_s"}


def test_a_generator_added_as_a_file_is_found_by_name(checkout, capsys):
    """Another kind of traffic is a generator file of its own beside the
    others, named by its mix; no file that is there is edited."""
    with open(os.path.join(checkout, "benchmark/traffic/paced.py"), "w") as f:
        f.write(
            "from benchmark.traffic import Request\n"
            "def requests(mix, vocab, seed, rate_rps=None):\n"
            "    i = 0\n"
            "    while True:\n"
            "        yield Request(i, i / rate_rps, [seed % vocab] * \n"
            "                      mix['prompt'], mix['output'])\n"
            "        i += 1\n")
    _write(os.path.join(checkout, "benchmark/traffic/paced.json"),
           {"generator": "paced", "loop": "open", "prompt": 9, "output": 5,
            "preroll_s": 0.3, "drain_s": 30})
    cell = add_cell(checkout, "paced")
    line, extra = rehearse(checkout, capsys, cell, 0)
    assert line["correct"], extra["why_not"]
    assert line["attempted"] >= 8 and line["failed"] == 0
    # a mix that names a generator nobody added is refused before any run
    _write(os.path.join(checkout, "benchmark/traffic/paced.json"),
           {"generator": "absent", "loop": "open"})
    with pytest.raises(mf.ManifestError):
        mf.validate(mf.load(checkout), checkout)


#: a later PR's block file: the plain reference of a layer whose MLP is
#: a top-1 choice among expert MLPs, every token served by its expert
#: (``moe_dropless``) — a block the program runs and ``dense`` cannot check
TOP1_BLOCK = '''
"""Attention as in ``dense``; in the MLP's place ``moe_num_experts`` expert
MLPs, of which each token takes the one its router scores highest, scaled
by that score's softmax probability."""
import jax
import jax.numpy as jnp

from benchmark.blocks import dense

SCOPES = ("router", "experts")
PUBLISHED_TO_FIELD = {"num_experts": "moe_num_experts",
                      "num_experts_per_tok": "moe_top_k"}


def _experts(h, lp, arch):
    probs = jax.nn.softmax(h @ lp["router_wg"], axis=-1)
    pick = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, pick[:, None], axis=-1)
    up = jnp.einsum("th,thm->tm", h, lp["w_in"][pick])
    y = jax.nn.silu(jnp.einsum("th,thm->tm", h, lp["w_gate"][pick])) * up
    return gate * jnp.einsum("tm,tmh->th", y, lp["w_out"][pick])


def logits(params, tokens, arch, q_block=1024):
    return dense.logits(params, tokens, arch, q_block, mlp=_experts)


def loss(params, input_ids, arch, q_block=1024):
    return dense.loss(params, input_ids, arch, q_block, mlp=_experts)


def matmul_params(arch):
    h, m = arch["hidden_size"], arch["intermediate_size"]
    per_layer = dense.attention_matmul_params(arch) \\
        + h * arch["moe_num_experts"] + arch["moe_top_k"] * 3 * h * m
    return arch["num_layers"] * per_layer + h * arch["vocab_size"]
'''


def _harness_hashes(root):
    out = {}
    for top, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(top, name)
            if "__pycache__" not in path:
                with open(path, "rb") as f:
                    out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_block_added_as_files_is_found_by_name(checkout, capsys):
    """A later PR's configuration whose block the ``dense`` reference does
    not cover: a block file, the configuration's file that names it, a
    mix, a cell and the entries — no file that was there is edited."""
    from benchmark.model import check_consistent

    before = _harness_hashes(checkout)
    with open(os.path.join(checkout, "benchmark/blocks/top1.py"), "w") as f:
        f.write(TOP1_BLOCK)
    arch = dict(TINY_MISTRAL["transformer_config"], moe_num_experts=4,
                moe_top_k=1, moe_dropless=True)
    config = dict(TINY_MISTRAL, block="top1", num_experts=4,
                  num_experts_per_tok=1, transformer_config=arch)
    _write(os.path.join(checkout, "benchmark/configs/tiny-top1.json"), config)
    _write(os.path.join(checkout, "benchmark/traffic/few.json"),
           dict(TRAFFIC["batch"], schedule_seed=2))
    _write(os.path.join(checkout,
                        "benchmark/workloads/tiny-top1.few.json"),
           {"runner": "serve"})
    manifest = mf.load(checkout)
    manifest["configs"].append(
        {"name": "tiny-top1", "source": "a later PR's",
         "file": "benchmark/configs/tiny-top1.json", "reduced": [],
         "why": "top-1 dropless experts"})
    manifest["workloads"].append(
        {"name": "tiny-top1.few", "config": "tiny-top1", "traffic": "few",
         "chips": 1, "why": "a later PR's cell"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "mistral-7b.batch" in m.get("workloads", []):
            m["workloads"].append("tiny-top1.few")
    _write(os.path.join(checkout, "BENCHMARK.json"), manifest)
    mf.validate(manifest, checkout)

    line, extra = rehearse(checkout, capsys, "tiny-top1.few", 0)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    check = extra["counters"]["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    # what the block adds is read where the harness reads it
    info = mf.resolve(manifest, "tiny-top1.few", checkout)
    assert info["block"].SCOPES == ("router", "experts")
    dense = mf.resolve(manifest, "mistral-7b.batch", checkout)["block"]
    assert info["block"].matmul_params(arch) == dense.matmul_params(arch) \
        + 2 * 64 * 4        # one expert of the four, and a router a layer
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        check_consistent(dict(config, num_experts_per_tok=2), info["block"])
    assert _harness_hashes(checkout).items() >= before.items()
    # the lookup is live: under the dense block the same configuration is
    # held to equations that are not its own, and is refused (the stacked
    # expert weights do not fit the dense MLP's shapes)
    _write(os.path.join(checkout, "benchmark/configs/tiny-top1.json"),
           dict(config, block="dense"))
    with pytest.raises(TypeError, match="carry"):
        rehearse(checkout, capsys, "tiny-top1.few", 0, seed="6")


def test_the_tolerances_are_measured_not_asserted(checkout, capsys):
    """``benchmark.tolerance`` at a tiny size: the float32 engine agrees
    with the reference, and weights through fp8 fail the cell's check."""
    from benchmark import tolerance

    capsys.readouterr()
    assert tolerance.main(["--config", "mistral-7b", "--prompt-tokens", "40"],
                          root=checkout) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert [x["variant"] for x in lines] == \
        ["float32", "served", "fp8_weights"]
    assert [x["within"] for x in lines] == [True, True, False]
    assert lines[0]["rms_rel_err"] < 1e-4 < 1e-2 < lines[2]["rms_rel_err"]
    assert all(x["platform"] == "cpu" for x in lines)


def _command(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "pythia-1.4b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_measure_off_the_chip():
    done = _command(REPO)
    assert done.returncode != 0
    assert done.stdout.strip() == ""            # no result line
    assert "not 'tpu'" in done.stderr


def test_the_command_needs_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    its paths there is nothing to measure: non-zero, no result."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(root, "tests", "benchmark"))
    done = _command(root)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
