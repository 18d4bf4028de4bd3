"""Environment packaging: templates, archives, content hashes, wheel builds.

The push pipeline (reference env.py:1039-1660): gitignore-filtered tar
archive + deterministic content hash (drift detection between local dir and
hub version, reference :365-409) + optional wheel build for pip installs.
"""

from __future__ import annotations

import fnmatch
import gzip
import hashlib
import io
import subprocess
import sys
import tarfile
import tomllib
from pathlib import Path

DEFAULT_EXCLUDES = [
    ".prime",  # local hub-link state (provenance.py) — never ships or hashes
    ".git",
    "__pycache__",
    "*.pyc",
    ".venv",
    "venv",
    "dist",
    "build",
    "*.egg-info",
    ".pytest_cache",
    "outputs",
    ".env",
]

ENV_TOML_TEMPLATE = """\
[environment]
name = "{name}"
version = "0.1.0"
description = ""
tags = []

[tpu]
# TPU requirements for this environment (checked at install on a slice)
tpu_type = "v5e"
min_chips = 1

[eval]
dataset = "data/eval.jsonl"
max_new_tokens = 256
"""

PYPROJECT_TEMPLATE = """\
[build-system]
requires = ["setuptools>=68"]
build-backend = "setuptools.build_meta"

[project]
name = "{name}"
version = "0.1.0"
description = "prime environment: {name}"
requires-python = ">=3.10"
"""

MAIN_TEMPLATE = '''\
"""Environment entry point: load_environment() -> examples + scorer."""


def load_environment():
    return {{"name": "{name}"}}
'''


def _load_gitignore(env_dir: Path) -> list[str]:
    patterns = list(DEFAULT_EXCLUDES)
    gitignore = env_dir / ".gitignore"
    if gitignore.exists():
        for line in gitignore.read_text().splitlines():
            line = line.strip()
            if line and not line.startswith("#"):
                patterns.append(line.rstrip("/"))
    return patterns


def _excluded(rel_path: str, patterns: list[str]) -> bool:
    parts = rel_path.split("/")
    for pattern in patterns:
        if any(fnmatch.fnmatch(part, pattern) for part in parts):
            return True
        if fnmatch.fnmatch(rel_path, pattern):
            return True
    return False


def iter_env_files(env_dir: str | Path) -> list[Path]:
    import os

    env_dir = Path(env_dir)
    patterns = _load_gitignore(env_dir)
    files = []
    for dirpath, dirnames, filenames in os.walk(env_dir):
        rel_dir = Path(dirpath).relative_to(env_dir).as_posix()
        # prune excluded directories so .venv/.git trees are never walked
        dirnames[:] = sorted(
            d for d in dirnames
            if not _excluded(f"{rel_dir}/{d}" if rel_dir != "." else d, patterns)
        )
        for name in sorted(filenames):
            rel = f"{rel_dir}/{name}" if rel_dir != "." else name
            if not _excluded(rel, patterns):
                files.append(Path(dirpath) / name)
    files.sort()
    return files


def content_hash(env_dir: str | Path) -> str:
    """Deterministic hash of the (filtered) env contents — drift detection."""
    env_dir = Path(env_dir)
    digest = hashlib.sha256()
    for path in iter_env_files(env_dir):
        rel = path.relative_to(env_dir).as_posix()
        digest.update(rel.encode())
        digest.update(b"\0")
        digest.update(path.read_bytes())
        digest.update(b"\0")
    return digest.hexdigest()


def build_archive(env_dir: str | Path) -> bytes:
    """Deterministic tar.gz of the filtered env dir (mtime/uid zeroed).

    The gzip layer is opened explicitly with ``mtime=0``: ``tarfile``'s
    ``w:gz`` mode stamps the CURRENT time into the gzip header, so two
    builds of identical content straddling a second boundary would differ
    byte-for-byte (caught by the packaging-determinism property test)."""
    env_dir = Path(env_dir)
    buffer = io.BytesIO()
    with gzip.GzipFile(fileobj=buffer, mode="wb", compresslevel=6, mtime=0) as gz:
        with tarfile.open(fileobj=gz, mode="w") as tar:
            for path in iter_env_files(env_dir):
                rel = path.relative_to(env_dir).as_posix()
                info = tarfile.TarInfo(name=rel)
                data = path.read_bytes()
                info.size = len(data)
                info.mtime = 0
                info.uid = info.gid = 0
                info.uname = info.gname = ""
                tar.addfile(info, io.BytesIO(data))
    return buffer.getvalue()


def extract_archive(data: bytes, target_dir: str | Path) -> None:
    target_dir = Path(target_dir)
    target_dir.mkdir(parents=True, exist_ok=True)
    with tarfile.open(fileobj=io.BytesIO(data), mode="r:gz") as tar:
        tar.extractall(target_dir, filter="data")


def read_env_metadata(env_dir: str | Path) -> dict:
    """Parse env.toml (name/version/tpu requirements)."""
    env_toml = Path(env_dir) / "env.toml"
    if not env_toml.exists():
        raise FileNotFoundError(f"No env.toml in {env_dir} — run `prime env init` first")
    data = tomllib.loads(env_toml.read_text())
    env = data.get("environment", {})
    if not env.get("name"):
        raise ValueError("env.toml [environment] must set a name")
    return {
        "name": env["name"],
        "version": env.get("version", "0.1.0"),
        "description": env.get("description", ""),
        "tags": env.get("tags", []),
        "tpu": data.get("tpu", {}),
        "eval": data.get("eval", {}),
    }


def write_env_template(env_dir: str | Path, name: str) -> list[Path]:
    """`prime env init`: scaffold env.toml, pyproject.toml, main module."""
    env_dir = Path(env_dir)
    env_dir.mkdir(parents=True, exist_ok=True)
    module = name.replace("-", "_")
    written = []
    for rel, contents in [
        ("env.toml", ENV_TOML_TEMPLATE.format(name=name)),
        ("pyproject.toml", PYPROJECT_TEMPLATE.format(name=name)),
        (f"{module}.py", MAIN_TEMPLATE.format(name=name)),
    ]:
        path = env_dir / rel
        if not path.exists():
            path.write_text(contents)
            written.append(path)
    return written


def build_wheel(env_dir: str | Path, out_dir: str | Path | None = None) -> Path:
    """Build a wheel from the env's pyproject (for pip installs from the hub)."""
    env_dir = Path(env_dir)
    out = Path(out_dir) if out_dir else env_dir / "dist"
    result = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", "--no-deps", "--no-build-isolation", "-w", str(out), str(env_dir)],
        capture_output=True,
        text=True,
    )
    if result.returncode != 0:
        raise RuntimeError(f"wheel build failed:\n{result.stderr[-2000:]}")
    wheels = sorted(out.glob("*.whl"), key=lambda p: p.stat().st_mtime)
    if not wheels:
        raise RuntimeError("wheel build produced no artifact")
    return wheels[-1]
