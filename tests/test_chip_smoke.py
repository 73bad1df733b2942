"""chip_smoke.py off the chip: the CPU rehearsal walks every leg at a tiny
scale and ends in the summary line and the result line the driver reads;
without the rehearsal flag, or without a TPU, the script and bench.py exit
non-zero and print no result; the compile cache sits where it was placed.

The chip run itself is not a test: it goes through the chip tool (see
.claude/skills/verify/SKILL.md), one process owning the chip."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env_changes):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = _ROOT
    env.update(env_changes)
    return subprocess.run([sys.executable, *args], cwd=_ROOT, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cpu_rehearsal_walks_every_leg():
    proc = _run(["chip_smoke.py", "--cpu-rehearsal"], JAX_PLATFORMS="cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    # the last line is the driver's contract: these keys and no others
    result = lines.pop()
    assert list(result) == ["ok", "device"] and result["ok"] is True
    assert list(result["device"]) == ["platform", "kind", "count"]
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int
    assert all(ln.get("platform") == "cpu" for ln in lines[:-1])
    assert {ln["leg"] for ln in lines[:-1]} >= {
        "device", "load", "q1", "q6", "q3c", "mutate", "serve"}
    summary = lines[-1]
    assert summary["device"] == result["device"]
    assert summary["ok"] is True
    assert summary["device"]["platform"] == "cpu"
    assert summary["reduced"] and summary["reduced"][0]["leg"] == "all"
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    by_leg = {ln["leg"]: ln for ln in lines[:-1]}
    for leg in ("q1", "q6", "mutate"):
        assert by_leg[leg]["lanes"]["host_fallbacks"] == 0
    assert by_leg["q3c"]["lanes"]["join_device_joins"] > 0
    assert by_leg["q3c"]["lanes"]["join_host_fallbacks"] == 0


def test_no_tpu_means_no_result():
    # a CPU-only environment without the rehearsal flag
    proc = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0 and proc.stdout == ""
    # the flag without JAX_PLATFORMS=cpu is refused before JAX starts
    proc = _run(["chip_smoke.py", "--cpu-rehearsal"])
    assert proc.returncode != 0 and proc.stdout == ""
    # bench.py on a CPU nobody asked for through JAX_PLATFORMS (pinned in
    # code here, so the child cannot take a chip where there is one)
    proc = _run(["-c", "import jax, runpy; "
                       "jax.config.update('jax_platforms', 'cpu'); "
                       "runpy.run_path('bench.py', run_name='__main__')"])
    assert proc.returncode == 2 and proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_compile_cache_dir_is_placed_from_outside_or_fixed(tmp_path):
    code = ("import snappydata_tpu, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    placed = _run(["-c", code], JAX_PLATFORMS="cpu",
                  JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert placed.stdout.strip() == str(tmp_path), placed.stderr[-500:]
    fixed = _run(["-c", code], JAX_PLATFORMS="cpu")
    assert fixed.stdout.strip() == os.path.join(_ROOT, ".jax_cache")
