"""End-to-end launch validation: the dry-run lowers and compiles a real
(arch x shape x mesh) cell in a subprocess (512 forced host devices), and
the roofline analyzer consumes its output."""

import json
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize(
    "arch,shape",
    [("mamba2_780m", "decode_32k"), ("hymba_1_5b", "long_500k")],
)
def test_dryrun_cell_subprocess(arch, shape):
    with tempfile.TemporaryDirectory() as d:
        out = Path(d) / "dryrun.json"
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", arch, "--shape", shape, "--mesh", "single",
             "--out", str(out)],
            # JAX_PLATFORMS=cpu: the dry-run compiles on forced host devices,
            # and a child must not take the TPU the test process may hold
            env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=420, cwd=str(REPO),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        recs = json.loads(out.read_text())
        assert len(recs) == 1
        rec = recs[0]
        assert rec["status"] == "ok", rec
        assert rec["chips"] == 256
        assert rec["fits_hbm"] is True
        assert rec["hlo"]["flops_per_device"] > 0

        # roofline consumes the record
        from repro.launch.roofline import analyze_record

        row = analyze_record(rec)
        assert row.dominant in ("compute", "memory", "collective")
        assert row.bound() > 0
