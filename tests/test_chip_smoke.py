"""CPU rehearsal of ``chip_smoke.py``: its job body, and its refusal to
report anything when no TPU is present."""

import importlib.util
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("use_kernels", [False, True], ids=["jnp", "kernels"])
def test_job_body_matches_numpy_reference(smoke, use_kernels):
    slots, clusters = 4, 512
    vocab = smoke.make_vocab(0, 3000)
    host = [smoke.make_batch(0, b, vocab, slots, 1 << 10, 1.1, 8) for b in range(2)]
    refs = [smoke.reference(*h, clusters) for h in host]
    batches = [tuple(jnp.asarray(a) for a in h) for h in host]
    lines = []
    results, tele = smoke.run_wordcount(
        batches, refs, num_slots=slots, num_clusters=clusters,
        use_kernels=use_kernels, log=lines.append)
    assert tele["batches"] == 2 and len(results) == 2
    assert all("values bit-identical, counts bit-identical" in ln for ln in lines)
    for res, (ref_values, ref_counts) in zip(results, refs):
        np.testing.assert_array_equal(res.values, ref_values)
        np.testing.assert_array_equal(res.counts, ref_counts)
        assert res.counts.sum() == slots * (1 << 10)


def test_job_body_rejects_a_wrong_reference(smoke):
    vocab = smoke.make_vocab(1, 100)
    host = smoke.make_batch(1, 0, vocab, 2, 256, 1.1, 8)
    ref_values, ref_counts = smoke.reference(*host, 64)
    ref_values[3, 0] += 1.0
    with pytest.raises(AssertionError, match="differs from the numpy reference"):
        smoke.run_wordcount([tuple(jnp.asarray(a) for a in host)],
                            [(ref_values, ref_counts)], num_slots=2,
                            num_clusters=64, use_kernels=False, log=lambda _: None)


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_script_alone_fails(tmp_path):
    """Without the repo next to it the script exits non-zero, no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, capture_output=True,
        text=True, timeout=300, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
