"""The four-chip cell ``codeqwen1.5-7b.code-tp4`` is found by name and runs
end to end: its rehearsal on four host CPU devices builds the (1, 4) mesh
the cell asks for, serves the ``code`` mix and passes the check against
the reference that draws the q, k and v biases, which the float8 control
fails."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run as bench

ROOT = Path(__file__).resolve().parents[2]
CELL = "codeqwen1.5-7b.code-tp4"


def test_the_cell_is_found_by_name():
    spec = bench.load_cell(CELL)
    assert spec["cell"]["chips"] == 4
    assert spec["config"]["mesh"] == [1, 4]
    assert spec["config"]["reference"] == "codeqwen"
    assert spec["mix"]["loop"] == "closed" and spec["mix"]["clients"] == 32
    cfg = bench.arch_config(spec["config"], rehearse=False)
    assert (cfg.num_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff,
            cfg.vocab, cfg.qkv_bias) == (32, 4096, 32, 4, 13440, 92416, True)
    assert [m["name"] for m in spec["end_to_end"]] == ["itl_p99_ms",
                                                       "setup_s"]
    assert all(m["workloads"] == [CELL] for m in spec["per_layer"])
    assert len(spec["per_layer"]) == 6


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "float8_control"])
def test_rehearsal_on_four_devices(control):
    """The program passes the rehearsal's limit; the float8 control judged
    in its place does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(2**32 + 17), "--seconds", "3", "--rehearse"]
        + (["--control"] if control else []), cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"] is (not control), got["check"]
    assert got["device"]["count"] == 4
    assert got["check"]["requests_checked"]["value"] == 8
