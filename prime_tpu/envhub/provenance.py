"""Release versioning + fork/upstream provenance for environments.

Reference behavior (commands/env.py:2010-2076 bump_version/bump_rc_version/
bump_post_version + :1073-1140 push --auto-bump/--rc/--post, :424
display_upstream_environment_info, utils/env_metadata.py): pushes can bump
the pyproject version in place first, and every push/pull records which hub
environment a local checkout tracks in ``.prime/env-metadata.json`` so later
pushes and evals can name their upstream.

TPU-repo shape: one module owns both concerns; the provenance record is a
single JSON file written atomically, and the version bumpers are pure
functions over PEP-440-ish strings.
"""

from __future__ import annotations

import json
import re
from datetime import datetime, timezone
from pathlib import Path

PROVENANCE_REL_PATH = Path(".prime") / "env-metadata.json"


# -- version bumping ----------------------------------------------------------


def bump_patch(version: str) -> str:
    """1.2.3 -> 1.2.4; pre/build suffixes on the patch are dropped
    (1.2.3rc1 -> 1.2.4); short versions grow a segment (1.2 -> 1.2.1)."""
    parts = version.split(".")
    if len(parts) >= 3:
        m = re.match(r"\d+", parts[2])
        if m is None:
            return f"{version}.1"
        return ".".join([*parts[:2], str(int(m.group()) + 1)])
    if len(parts) == 2:
        return f"{version}.1"
    return f"{version}.0.1"


def _bump_suffix(version: str, tag: str) -> str:
    m = re.match(rf"^(?P<base>.*?)(?:\.{tag}|{tag})(?P<num>\d+)$", version)
    if m:
        return f"{m.group('base')}.{tag}{int(m.group('num')) + 1}"
    base = re.sub(r"([+-].*)$", "", version)
    return f"{base}.{tag}0"


def bump_rc(version: str) -> str:
    """1.2.3 -> 1.2.3.rc0; 1.2.3.rc0 -> 1.2.3.rc1."""
    return _bump_suffix(version, "rc")


def bump_post(version: str) -> str:
    """1.2.3 -> 1.2.3.post0; 1.2.3.post0 -> 1.2.3.post1."""
    return _bump_suffix(version, "post")


def read_pyproject_version(env_dir: str | Path) -> str | None:
    """The [project] version in <env_dir>/pyproject.toml, or None."""
    import tomllib

    path = Path(env_dir) / "pyproject.toml"
    try:
        data = tomllib.loads(path.read_text())
    except (OSError, tomllib.TOMLDecodeError):
        return None
    version = data.get("project", {}).get("version")
    return version if isinstance(version, str) else None


def read_env_toml_version(env_dir: str | Path) -> str | None:
    """The [environment] version in <env_dir>/env.toml (what push uploads)."""
    import tomllib

    path = Path(env_dir) / "env.toml"
    try:
        data = tomllib.loads(path.read_text())
    except (OSError, tomllib.TOMLDecodeError):
        return None
    version = data.get("environment", {}).get("version")
    return version if isinstance(version, str) else None


def _rewrite_table_version(content: str, table: str, new_version: str) -> tuple[str, bool]:
    """Replace the ``version =`` line INSIDE ``[table]`` only — a version key
    in an unrelated earlier table (e.g. [tool.*]) must never be touched."""
    lines = content.splitlines(keepends=True)
    in_table = False
    for i, line in enumerate(lines):
        stripped = line.strip()
        if stripped.startswith("["):
            in_table = stripped == f"[{table}]"
            continue
        if in_table:
            replaced, n = re.subn(
                r'^(\s*version\s*=\s*)["\'][^"\']*["\']',
                rf'\g<1>"{new_version}"',
                line,
                count=1,
            )
            if n:
                lines[i] = replaced
                return "".join(lines), True
    return content, False


def bumped_version(env_dir: str | Path, mode: str) -> tuple[str, str]:
    """Apply one bump mode ('patch' | 'rc' | 'post') to the checkout.

    Both version carriers stay in sync: env.toml's [environment] version is
    what `env push` uploads, pyproject's [project] version is what the wheel
    build bakes in (a pyproject with no literal version line — dynamic
    versioning — is left alone). Returns (old, new); ValueError when no
    version line was found to rewrite."""
    env_dir = Path(env_dir)
    current = read_env_toml_version(env_dir) or read_pyproject_version(env_dir)
    if not current:
        raise ValueError(f"no version in {env_dir}/env.toml or pyproject.toml to bump")
    new = {"patch": bump_patch, "rc": bump_rc, "post": bump_post}[mode](current)
    rewritten = 0
    for name, table in (("env.toml", "environment"), ("pyproject.toml", "project")):
        path = env_dir / name
        if not path.exists():
            continue
        updated, changed = _rewrite_table_version(path.read_text(), table, new)
        if changed:
            path.write_text(updated)
            rewritten += 1
    if rewritten == 0:
        raise ValueError(
            f"no [environment]/[project] version line in {env_dir} to rewrite"
        )
    return current, new


# -- fork/upstream provenance -------------------------------------------------


def read_provenance(env_dir: str | Path) -> dict | None:
    """The checkout's hub-link record, or None when it was never linked."""
    path = Path(env_dir) / PROVENANCE_REL_PATH
    try:
        data = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return None
    return data if isinstance(data, dict) else None


def write_provenance(env_dir: str | Path, **fields) -> dict:
    """Merge ``fields`` into the checkout's record (created on demand);
    stamps ``updatedAt``. Returns the merged record."""
    path = Path(env_dir) / PROVENANCE_REL_PATH
    record = read_provenance(env_dir) or {}
    record.update({k: v for k, v in fields.items() if v is not None})
    record["updatedAt"] = datetime.now(timezone.utc).isoformat(timespec="seconds")
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(record, indent=2, sort_keys=True) + "\n")
    tmp.replace(path)
    return record


def upstream_display(record: dict | None) -> str | None:
    """'owner/name' when the record names its upstream environment."""
    if not record:
        return None
    name = record.get("name")
    if not name:
        return None
    owner = record.get("owner")
    return f"{owner}/{name}" if owner else str(name)
