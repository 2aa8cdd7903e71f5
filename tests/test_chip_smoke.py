"""chip_smoke.py's phases at tiny sizes on the CPU (Pallas interpreted).

The script's device check stays in ``main()``; here each phase runs the
same control flow and the same reference checks as on the chip.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import reduced_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def test_plan_phase_tiny():
    chip_smoke.plan_phase(512, queries=64)


def test_batch_phase_tiny():
    chip_smoke.batch_phase(members=4, n=256)


def test_decode_phase_tiny():
    chip_smoke.decode_phase(reduced_config("qwen2-0.5b"), slots=2,
                            max_seq=256, n_req=3, prompt=(40, 100),
                            max_new=4, bucket=64, block_k=32)


def test_sharded_phase_tiny_four_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        from repro.compat import make_mesh
        chip_smoke.sharded_phase(1024, make_mesh((4,), ("data",)))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    devices = [line.rsplit(" ", 1)[1] for line in r.stdout.splitlines()
               if line.startswith("four-chips: shard ")]
    assert sorted(devices) == ["0", "1", "2", "3"]
    assert "sharded CG matches one device" in r.stdout


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_follows_env(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []                     # JAX reads the variable itself
    monkeypatch.delenv(compile_cache.ENV)
    path = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert Path(path) == ROOT / ".jax_cache"
