"""Launch runner: config cards -> platform jobs (reference launch_runner.py).

A config card is a TOML file under ``<workspace>/.prime-lab/launch/``:

    [launch]
    kind = "train" | "eval"
    name = "sweep-lr3e4"          # optional display name

    [train]                       # kind=train: hosted-training TOML payload
    model = "llama3-8b"
    env = "arith-rl"
    ...

    [eval]                        # kind=eval: hosted eval config
    env = "arith-rl"
    model = "llama3-8b"
    tpu_type = "v5e-8"

The Lab shell lists cards in the launch section; launching submits through
the same clients the CLI uses and reports the created run id.
"""

from __future__ import annotations

import tomllib
from dataclasses import dataclass
from pathlib import Path
from typing import Any


class LaunchError(RuntimeError):
    pass


@dataclass
class LaunchCard:
    path: Path
    kind: str
    name: str
    payload: dict[str, Any]


def launch_dir(workspace: str | Path = ".") -> Path:
    return Path(workspace) / ".prime-lab" / "launch"


def scan_cards(workspace: str | Path = ".") -> list[LaunchCard]:
    cards = []
    base = launch_dir(workspace)
    if not base.exists():
        return cards
    for path in sorted(base.glob("*.toml")):
        try:
            data = tomllib.loads(path.read_text())
        except (OSError, tomllib.TOMLDecodeError):
            continue
        launch = data.get("launch", {})
        kind = launch.get("kind")
        if kind not in ("train", "eval"):
            continue
        cards.append(
            LaunchCard(
                path=path,
                kind=kind,
                name=launch.get("name", path.stem),
                payload=data.get(kind, {}),
            )
        )
    return cards


def format_toml(card: LaunchCard) -> str:
    """Serialize a card back to TOML (reference toml_format.py role). Flat
    scalar payloads only — exactly what scan_cards accepts."""

    def literal(value: Any) -> str:
        if isinstance(value, bool):
            return "true" if value else "false"
        if isinstance(value, (int, float)):
            return str(value)
        text = str(value).replace("\\", "\\\\").replace('"', '\\"')
        return f'"{text}"'

    def bare(key: str) -> str:
        # non-bare keys are quoted so they stay FLAT on reparse (an unquoted
        # dotted key would nest and corrupt the scalar payload contract)
        if key and key.replace("_", "").replace("-", "").isalnum():
            return key
        return literal(key)

    lines = ["[launch]", f'kind = "{card.kind}"', f"name = {literal(card.name)}", ""]
    lines.append(f"[{card.kind}]")
    for key, value in card.payload.items():
        lines.append(f"{bare(key)} = {literal(value)}")
    return "\n".join(lines) + "\n"


def save_card(card: LaunchCard) -> None:
    """Write the card to its path; a reparse failure means a bug in
    format_toml, surfaced as LaunchError rather than a corrupt card."""
    text = format_toml(card)
    try:
        reparsed = tomllib.loads(text)
    except tomllib.TOMLDecodeError as e:  # pragma: no cover - formatter bug
        raise LaunchError(f"card would not reparse: {e}") from e
    if reparsed.get("launch", {}).get("kind") != card.kind:
        raise LaunchError("card would lose its kind on reparse")  # pragma: no cover
    if reparsed.get(card.kind) != card.payload:
        raise LaunchError("card payload would not round-trip")
    card.path.parent.mkdir(parents=True, exist_ok=True)
    card.path.write_text(text)


def parse_field_value(text: str) -> Any:
    """Editor input -> typed TOML value (int / float / bool / string)."""
    stripped = text.strip()
    if stripped.lower() in ("true", "false"):
        return stripped.lower() == "true"
    for cast in (int, float):
        try:
            return cast(stripped)
        except ValueError:
            continue
    return stripped


def launch_card(card: LaunchCard, api_client) -> dict[str, Any]:
    """Submit a card through the platform clients. Returns {id, kind, status}."""
    if not card.payload:
        raise LaunchError(f"{card.path.name} has no [{card.kind}] payload")
    if card.kind == "train":
        from prime_tpu.api.rl import RLClient

        run = RLClient(api_client).create_run(card.payload)
        return {"id": run.run_id, "kind": "train", "status": run.status}
    if card.kind == "eval":
        from prime_tpu.evals import EvalsClient

        run = EvalsClient(api_client).create_hosted(card.payload)
        return {"id": run["hostedId"], "kind": "eval", "status": run["status"]}
    raise LaunchError(f"unknown card kind {card.kind!r}")
