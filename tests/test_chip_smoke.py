"""``chip_smoke.py`` off the chip: it must refuse to pass, and its phases'
control flow is rehearsed here with the tiny config on the CPU mesh — by
calling the phase functions, not through an option of the script.
"""

import dataclasses
import json
import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from deepspeed_tpu.models.transformer import TINY_TEST  # noqa: E402


def test_main_refuses_a_platform_that_is_not_tpu(capsys):
    """The no-fallback rule, tested: under the tests' CPU backend the
    script exits non-zero, says ``"ok": false`` and names the platform;
    the last line carries exactly the three device keys."""
    assert chip_smoke.main([]) != 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "'cpu'" in last["reason"] and "tpu" in last["reason"]
    assert last["device"] == {"platform": "cpu",
                              "kind": jax.devices()[0].device_kind,
                              "count": len(jax.devices())}


def test_main_fails_when_a_phase_raises(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "device_record", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})

    def broken(chips, seed):
        raise ValueError("kernel refused: q (8, 2048, 16, 128)")

    monkeypatch.setattr(chip_smoke, "run", broken)
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and "kernel refused" in last["reason"]


def test_last_line_is_the_drivers_contract(monkeypatch, capsys):
    """On success the last stdout line is one JSON object: ``ok`` and a
    ``device`` of exactly platform, kind and count, as JAX reports them."""
    monkeypatch.setattr(chip_smoke, "device_record", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4})
    monkeypatch.setattr(chip_smoke, "run", lambda chips, seed: None)
    assert chip_smoke.main(["--chips", "4"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
    assert list(json.loads(last)["device"]) == ["platform", "kind", "count"]


def test_more_chips_asked_than_present(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "device_record", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert chip_smoke.main(["--chips", "4"]) == 1
    assert "4 chips asked for, 1 present" in capsys.readouterr().out


_TINY_ENGINE = {"kv_block_size": 8, "kv_blocks": 64, "max_chunk_tokens": 16,
                "max_ragged_batch_size": 48, "max_ragged_sequence_count": 8}


def test_serve_phase_passes_its_own_checks_on_the_cpu_mesh(capsys):
    out = chip_smoke.run_phase(
        "serve", chip_smoke.serve_phase, cfg=TINY_TEST, seed=0,
        prompt_lens=[8, 40, 16, 24, 32, 8, 16, 40], max_new=6,
        engine_cfg=_TINY_ENGINE)
    assert out["requests"] == 8 and out["kv_blocks_returned"] == 64
    assert out["logits_max_rel_err"] <= out["tolerance"]
    assert out["pallas_call_in_forward"] is False      # CPU: the XLA gather
    assert [8, 16] in [list(s) for s in out["bucket_shapes"]] \
        or len(out["bucket_shapes"]) > 1               # chunks mixed in
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "serve" and line["platform"] == "cpu"
    assert line["memory"] == {"memory_stats": "not reported by this backend"}


def test_train_phase_passes_its_own_checks_on_the_cpu_mesh(devices8):
    cfg = dataclasses.replace(TINY_TEST, num_layers=1, remat=True)
    out = chip_smoke.train_phase(cfg, seed=0, batch=8, seq=64, steps=5,
                                 full_layers=TINY_TEST.num_layers)
    assert out["reduced"] == {"num_layers": [2, 1]}
    assert out["losses"][-1] < out["losses"][0]
    assert out["layouts_tuned"] is False               # a TPU-only step
    assert out["flash_call_in_micro_step"] is False


def test_four_chip_phases_on_four_virtual_devices(monkeypatch):
    """The ``--chips 4`` phases on four of the CPU mesh's devices: ZeRO-3
    over fsdp spreads every large parameter evenly and matches the
    one-device losses; tensor-parallel serving matches the one-device
    engine with the pools split by kv-head."""
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:4])
    cfg = dataclasses.replace(TINY_TEST, num_layers=1, num_kv_heads=4,
                              hidden_size=256, intermediate_size=4096,
                              remat=True)
    out = chip_smoke.zero3_phase(cfg, seed=0, batch=8, seq=64, steps=3)
    assert out["mesh"] == {"fsdp": 4}
    assert out["param_share_per_device"] == [0.25, 0.25]
    out = chip_smoke.tensor_serve_phase(
        cfg, seed=0, engine_cfg=_TINY_ENGINE, lens=(16, 9, 12))
    assert out["mesh"] == {"tensor": 4} and out["pool_shard"][2] == 1


# ------------------------------------------- one process per chip, one cache

def test_replica_that_finds_its_chip_taken_exits_with_the_cause(
        monkeypatch, tmp_path, capsys):
    """A chip belongs to one process: libtpu refuses the second at once,
    and the replica server turns that into exit code 3 and a message —
    not a traceback, not a wait."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_replica", os.path.join(os.path.dirname(chip_smoke.__file__),
                                      "scripts", "serve_replica.py"))
    serve_replica = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve_replica)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def taken():
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: "
                           "libtpu multi-process lockfile")

    monkeypatch.setattr(jax, "devices", taken)
    path = tmp_path / "spec.json"
    path.write_text(json.dumps({"model": {}}))
    assert serve_replica.main(["--spec", str(path)]) == 3
    err = capsys.readouterr().err
    assert "a chip belongs to one process" in err and "lockfile" in err


def test_compile_cache_is_placed_from_outside_or_under_the_checkout(
        monkeypatch, tmp_path):
    from deepspeed_tpu.utils import compile_cache

    old = jax.config.jax_compilation_cache_dir
    try:
        # placed by the environment: JAX reads it, the code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old
        # not placed: a fixed path under the checkout, never a temp dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.abspath(chip_smoke.__file__))
        assert compile_cache.enable_compile_cache() == \
            os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    with open(os.path.join(repo, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
