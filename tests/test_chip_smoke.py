"""chip_smoke.py's phases on the CPU at the smoke widths
(``configs/cosmosann.py:smoke()``), and its refusal to run without a TPU.

The phases are the same functions the chip run calls; here the Pallas
kernels run in interpret mode and the spmd phase runs on four virtual CPU
devices in a child process (the device count is fixed when JAX starts).
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.configs import cosmosann

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_smoke_phases_at_tiny_size(smoke):
    cfg = cosmosann.smoke()
    n, n_new = 1500, 8
    corpus, _ = smoke.make_corpus(0, n, cfg.dim)
    ids = np.arange(n)
    svc = smoke.build_service(cfg, max_vectors=n)
    smoke.load(svc, ids[:-n_new], corpus[:-n_new], chunk=500)
    assert svc.collection.num_docs == n - n_new
    queries = smoke.make_queries(0, 32, cfg.dim)
    k, L = cfg.k, cfg.L_search
    q = smoke.query_phase(svc, queries, corpus[:-n_new], ids[:-n_new], k, L)
    assert q["recall"] >= smoke.RECALL_FLOOR
    assert smoke.filtered_phase(svc, queries[:8], k, L)["returned"] > 0
    g = smoke.guarantee_phase(svc, ids[-n_new:], corpus[-n_new:], k, L)
    assert g["deleted"] == n - n_new
    assert svc.collection.num_docs == n - 1
    report = smoke.kernel_phase(svc, queries, k, L, n_rows=1024)
    assert set(report) == {"pq_adc", f"topk_select[L={k}]",
                           f"topk_select[L={L}]", "flat_l2", "pq_encode"}


def test_smoke_spmd_phase_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location("s", {str(SCRIPT)!r})
        s = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(s)
        from repro.configs import cosmosann
        out = s.spmd_phase(cosmosann.smoke(), 0, 1100, 4)
        print(json.dumps(dict(placement=out["placement"],
                              same=bool(out["ids_identical"]))))
    """)
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["same"]
    assert sorted(map(tuple, out["placement"])) == [(i, i) for i in range(4)]


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_smoke_exits_nonzero_without_tpu_or_repo(tmp_path, where):
    script = SCRIPT
    if where == "script_alone":
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, script)
    env = _env()
    if where == "script_alone":
        env.pop("PYTHONPATH")
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=300, cwd=script.parent, env=env)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
