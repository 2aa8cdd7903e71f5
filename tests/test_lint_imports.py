"""Lint: version-sensitive JAX APIs are only touched via repro.compat.

Every seed failure of this repo traced to JAX API moves (shard_map
location/kwargs, AbstractMesh ctor, lax.axis_size). PR 1 routed them all
through ``src/repro/compat.py``; this test keeps it that way — new code
must import the wrappers, not the moving targets.
"""
import re
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

# import/usage forms that break across JAX releases (fine only in compat.py)
FORBIDDEN = (
    r"jax\.experimental\.shard_map",
    r"from\s+jax\s+import\s+[^\n]*\bshard_map\b",
    r"jax\.shard_map",
    r"\bAbstractMesh\b",
    r"\blax\.axis_size\b",
    r"\bcheck_rep\b",
    r"jax\.make_mesh",          # meshes come from compat (AxisType.Auto)
)


def test_version_sensitive_jax_imports_only_in_compat():
    offenders = []
    for path in sorted(SRC.rglob("*.py")):
        if path.name == "compat.py":
            continue
        text = path.read_text()
        for pat in FORBIDDEN:
            for m in re.finditer(pat, text):
                line = text[:m.start()].count("\n") + 1
                offenders.append(f"{path.relative_to(SRC.parent)}:{line} "
                                 f"matches {pat!r}")
    assert not offenders, (
        "version-sensitive JAX usage outside repro/compat.py — import the "
        "compat wrapper instead:\n" + "\n".join(offenders))
