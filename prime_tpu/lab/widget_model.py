"""Typed widget-payload model: normalize/repair/reject agent widget calls.

Reference role: prime_lab_app/agent_widget_model.py:1-1168 + agent_cards.py
:1-536 — the layer between raw agent tool-call JSON and the TUI. Agents emit
malformed payloads constantly (numbers as strings, null holes, scalar where
an array belongs, 10k-row tables); the previous shallow check
(widgets.validate_widget_call) only gated types, so anything past it was
rendered best-effort. This module gives every widget a typed contract:

- **repair** what is safely repairable — coerce numeric strings, stringify
  scalar options, drop null/empty/non-finite entries, dedupe, cap sizes —
  and RECORD each repair so the TUI can show "repaired: ..." instead of
  silently rendering something the agent didn't say;
- **reject** what isn't — unknown tool, missing required keys, payloads
  empty after repair — with a reason string the chat renders as an error
  widget (never a crash, never a silent misrender);
- **round-trip state**: the stamps the chat screen writes back into a
  rendered widget's args (``selected``, ``saved_card``) survive
  re-normalization, so re-rendering a transcript keeps interaction state;
- **card lifecycle**: a normalized ``launch_run`` payload converts to a
  typed launch-card payload (kind mapped onto the card taxonomy, numerics
  actually numeric) so the card on disk — and the TOML the user edits —
  has real types, not stringly-typed leftovers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

MAX_OPTIONS = 24
MAX_ROWS = 100
MAX_POINTS = 512
MAX_PATCH_LINES = 400
# launch-config fields that must be numeric on the card; agents routinely
# send them as strings ("limit": "64")
INT_CONFIG_FIELDS = ("limit", "batch_size", "max_new_tokens", "epochs", "draft_len", "seed")
FLOAT_CONFIG_FIELDS = ("temperature", "learning_rate", "top_p", "beta", "clip_eps")
# stamps the chat screen writes back into rendered args; normalization must
# carry them through unchanged (widget state round-trip). ``form_values``
# holds the user's form edits, ``form_errors`` the last typed-parse failures
# (prefixed: a bare "values" stamp would collide with show_chart's payload).
STATE_KEYS = ("selected", "saved_card", "command", "form_values", "form_errors")

FORM_KINDS = ("eval", "rl", "gepa")
FORM_INT_FIELDS = ("rollouts_per_example", "max_steps", "seq_len")


class WidgetValidationError(Exception):
    """The payload is unusable even after repair; the message says why."""


@dataclass
class NormalizedWidget:
    name: str
    args: dict[str, Any]
    repairs: tuple[str, ...] = ()

    def with_state_from(self, raw_args: dict[str, Any]) -> "NormalizedWidget":
        for key in STATE_KEYS:
            if isinstance(raw_args, dict) and key in raw_args:
                self.args[key] = raw_args[key]
        return self


def _coerce_number(value: Any) -> float | int | None:
    """A number, a numeric string, or None; NaN/inf count as unusable."""
    if isinstance(value, bool):
        return None
    if isinstance(value, (int, float)):
        return value if math.isfinite(value) else None
    if isinstance(value, str):
        text = value.strip()
        try:
            number = int(text)
        except ValueError:
            try:
                number = float(text)
            except ValueError:
                return None
        return number if math.isfinite(number) else None
    return None


def _title(args: dict[str, Any], repairs: list[str]) -> dict[str, Any]:
    out: dict[str, Any] = {}
    if "title" in args and args["title"] is not None:
        if isinstance(args["title"], str):
            out["title"] = args["title"]
        else:
            out["title"] = str(args["title"])
            repairs.append("title coerced to string")
    return out


def _normalize_choose(args: dict[str, Any], repairs: list[str]) -> dict[str, Any]:
    raw = args.get("options")
    if not isinstance(raw, list):
        raise WidgetValidationError("choose: options must be an array of strings")
    options: list[str] = []
    for item in raw:
        if item is None:
            repairs.append("dropped null option")
            continue
        text = item if isinstance(item, str) else str(item)
        if not isinstance(item, str):
            repairs.append(f"option {text[:20]!r} coerced to string")
        text = text.strip()
        if not text:
            repairs.append("dropped empty option")
            continue
        if text in options:
            repairs.append(f"dropped duplicate option {text[:20]!r}")
            continue
        options.append(text)
    if not options:
        raise WidgetValidationError("choose: no usable options after repair")
    if len(options) > MAX_OPTIONS:
        repairs.append(f"options capped at {MAX_OPTIONS} (got {len(options)})")
        options = options[:MAX_OPTIONS]
    return {**_title(args, repairs), "options": options}


def _normalize_table(args: dict[str, Any], repairs: list[str]) -> dict[str, Any]:
    raw = args.get("rows")
    if not isinstance(raw, list):
        raise WidgetValidationError("show_table: rows must be an array of objects")
    rows = []
    for row in raw:
        if isinstance(row, dict):
            rows.append({str(k): v for k, v in row.items()})
        else:
            repairs.append(f"dropped non-object row {str(row)[:20]!r}")
    if not rows:
        raise WidgetValidationError("show_table: no object rows after repair")
    if len(rows) > MAX_ROWS:
        repairs.append(f"rows capped at {MAX_ROWS} (got {len(rows)})")
        rows = rows[:MAX_ROWS]
    return {**_title(args, repairs), "rows": rows}


def _normalize_chart(args: dict[str, Any], repairs: list[str]) -> dict[str, Any]:
    raw = args.get("values")
    if not isinstance(raw, list):
        raise WidgetValidationError("show_chart: values must be an array of numbers")
    values: list[float | int] = []
    for item in raw:
        number = _coerce_number(item)
        if number is None:
            repairs.append(f"dropped non-numeric value {str(item)[:20]!r}")
            continue
        if not isinstance(item, (int, float)) or isinstance(item, bool):
            repairs.append(f"value {number} coerced from {type(item).__name__}")
        values.append(number)
    if not values:
        raise WidgetValidationError("show_chart: no numeric values after repair")
    if len(values) > MAX_POINTS:
        repairs.append(f"values downsampled to {MAX_POINTS} points (got {len(values)})")
        step = len(values) / MAX_POINTS
        values = [values[int(i * step)] for i in range(MAX_POINTS)]
    return {**_title(args, repairs), "values": values}


def _normalize_launch(args: dict[str, Any], repairs: list[str]) -> dict[str, Any]:
    kind = args.get("kind")
    if not isinstance(kind, str) or kind not in ("eval", "training"):
        raise WidgetValidationError(
            f"launch_run: kind must be 'eval' or 'training', got {str(kind)[:20]!r}"
        )
    raw = args.get("config")
    if not isinstance(raw, dict):
        raise WidgetValidationError("launch_run: config must be an object")
    config: dict[str, Any] = {}
    for key, value in raw.items():
        key = str(key)
        if value is None:
            repairs.append(f"dropped null config field {key!r}")
            continue
        if key in INT_CONFIG_FIELDS:
            number = _coerce_number(value)
            if number is None:
                repairs.append(f"dropped non-numeric {key!r}={str(value)[:20]!r}")
                continue
            if not isinstance(value, int) or isinstance(value, bool):
                repairs.append(f"{key} coerced to int")
            config[key] = int(number)
        elif key in FLOAT_CONFIG_FIELDS:
            number = _coerce_number(value)
            if number is None:
                repairs.append(f"dropped non-numeric {key!r}={str(value)[:20]!r}")
                continue
            if isinstance(value, str):
                repairs.append(f"{key} coerced to float")
            config[key] = float(number)
        elif isinstance(value, (str, int, float, bool)):
            config[key] = value
        else:
            repairs.append(f"dropped non-scalar config field {key!r}")
    if not config:
        raise WidgetValidationError("launch_run: no usable config fields after repair")
    return {"kind": kind, "config": config}


def _normalize_patch(args: dict[str, Any], repairs: list[str]) -> dict[str, Any]:
    raw = args.get("patch")
    if raw is None:
        raise WidgetValidationError("show_patch: patch is required")
    text = raw if isinstance(raw, str) else str(raw)
    if not isinstance(raw, str):
        repairs.append("patch coerced to string")
    if not text.strip():
        raise WidgetValidationError("show_patch: patch is empty")
    lines = text.splitlines()
    if len(lines) > MAX_PATCH_LINES:
        repairs.append(f"patch truncated to {MAX_PATCH_LINES} lines (got {len(lines)})")
        text = "\n".join(lines[:MAX_PATCH_LINES])
    return {**_title(args, repairs), "patch": text}


def _normalize_form(args: dict[str, Any], repairs: list[str]) -> dict[str, Any]:
    """configure_run: an editable run form (reference run_launcher/
    config_editor widget kinds). kind picks the field schedule; env seeds the
    environment field; config overrides the per-kind defaults."""
    kind = args.get("kind")
    if isinstance(kind, str):
        kind = {"training": "rl", "train": "rl"}.get(kind.strip(), kind.strip())
        if kind != args.get("kind"):
            repairs.append(f"kind {args.get('kind')!r} mapped to {kind!r}")
    if kind not in FORM_KINDS:
        raise WidgetValidationError(
            f"configure_run: kind must be one of {sorted(FORM_KINDS)}, "
            f"got {str(args.get('kind'))[:20]!r}"
        )
    out: dict[str, Any] = {**_title(args, repairs), "kind": kind}
    env = args.get("env")
    if env is not None:
        if not isinstance(env, str):
            env = str(env)
            repairs.append("env coerced to string")
        if env.strip():
            out["env"] = env.strip()
    raw = args.get("config")
    if raw is not None and not isinstance(raw, dict):
        repairs.append("dropped non-object config")
        raw = None
    if isinstance(raw, dict):
        config: dict[str, Any] = {}
        for key, value in raw.items():
            key = str(key)
            if value is None:
                repairs.append(f"dropped null config field {key!r}")
                continue
            if key in INT_CONFIG_FIELDS or key in FORM_INT_FIELDS:
                number = _coerce_number(value)
                if number is None:
                    repairs.append(f"dropped non-numeric {key!r}={str(value)[:20]!r}")
                    continue
                config[key] = int(number)
            elif isinstance(value, (str, int, float, bool)):
                config[key] = value
            else:
                repairs.append(f"dropped non-scalar config field {key!r}")
        if config:
            out["config"] = config
    return out


_NORMALIZERS = {
    "choose": _normalize_choose,
    "show_table": _normalize_table,
    "show_chart": _normalize_chart,
    "launch_run": _normalize_launch,
    "show_patch": _normalize_patch,
    "configure_run": _normalize_form,
}


def normalize_widget_call(name: str, args: Any) -> NormalizedWidget:
    """Typed repair-or-reject for one widget call.

    Returns the normalized payload with a record of every repair applied, or
    raises :class:`WidgetValidationError` with a reason the TUI can render.
    Interaction stamps (``selected``/``saved_card``) round-trip untouched.
    """
    normalizer = _NORMALIZERS.get(name)
    if normalizer is None:
        raise WidgetValidationError(f"unknown widget tool {name!r}")
    if not isinstance(args, dict):
        raise WidgetValidationError(f"{name}: args must be an object")
    repairs: list[str] = []
    normalized = normalizer(args, repairs)
    return NormalizedWidget(name=name, args=normalized, repairs=tuple(repairs)).with_state_from(
        args
    )


# -- typed run form (reference agent_widget_model.py field-spec layer) --------


@dataclass(frozen=True)
class FieldSpec:
    """One editable field of a run form (reference AgentWidgetFieldSpec)."""

    name: str
    label: str
    value: str
    input_type: str = "text"  # "text" | "integer"
    disabled: bool = False
    widget: str = "input"  # "input" | "select"
    options: tuple[tuple[str, str], ...] = ()  # (label, value)


@dataclass(frozen=True)
class ActionSpec:
    """One action a form exposes (reference AgentWidgetActionSpec)."""

    name: str
    label: str
    variant: str = "default"


@dataclass(frozen=True)
class FormModel:
    """Logical run-configuration form, independent of the rendering skin.

    ``extras`` are agent-proposed config fields outside the editable
    schedule (e.g. temperature, seed): not editable, but visible in the
    render and carried onto the launched card — a proposal must not behave
    differently between launch_run and configure_run."""

    kind: str
    title: str
    fields: tuple[FieldSpec, ...]
    actions: tuple[ActionSpec, ...]
    extras: tuple[tuple[str, Any], ...] = ()


# (name, label, input_type, default, disabled) per form kind — defaults
# mirror the reference's seeded values, renamed to this repo's config
# vocabulary (limit/max_new_tokens, not num_examples/max_tokens)
_FORM_SCHEDULES: dict[str, tuple[tuple[str, str, str, str, bool], ...]] = {
    "eval": (
        ("env", "Environment", "text", "", False),
        ("model", "Model", "text", "", False),
        ("limit", "Examples", "integer", "50", False),
        ("rollouts_per_example", "Rollouts per example", "integer", "3", False),
        ("max_new_tokens", "Max new tokens", "integer", "1024", False),
        ("max_concurrent", "Max concurrent", "text", "auto", False),
    ),
    "rl": (
        ("env", "Environment", "text", "", False),
        ("model", "Model", "text", "", False),
        ("max_steps", "Steps", "integer", "100", False),
        ("rollouts_per_example", "Rollouts per example", "integer", "8", False),
        ("batch_size", "Rollouts per batch", "integer", "256", False),
        ("max_new_tokens", "Max new tokens", "integer", "8192", False),
        ("seq_len", "Seq len", "integer", "", True),
    ),
    "gepa": (
        ("env", "Environment", "text", "", False),
        ("model", "Model", "text", "", False),
    ),
}

_FORM_TITLES = {"eval": "Evaluate", "rl": "Train", "gepa": "Optimize"}


# render_widget repaints every transcript widget on every keystroke; without
# a cache each frame would re-read configs/endpoints.toml and every env.toml
# (TUI render hot path). A short TTL keeps edits visible within a beat.
_OPTIONS_CACHE: dict[tuple[str, ...], tuple[float, Any]] = {}
_OPTIONS_TTL_S = 2.0


def _cached(key: tuple[str, ...], compute):
    import time

    now = time.monotonic()
    hit = _OPTIONS_CACHE.get(key)
    if hit is not None and now - hit[0] < _OPTIONS_TTL_S:
        return hit[1]
    value = compute()
    _OPTIONS_CACHE[key] = (now, value)
    return value


def model_options(workspace: Any = None, kind: str = "eval") -> tuple[tuple[str, str], ...]:
    """(label, value) model choices: local presets plus the workspace's
    configs/endpoints.toml aliases (reference _widget_model_options — there
    the options come from the training API / endpoint registry; here the
    preset table IS the trainable set, and aliases are serving endpoints, so
    rl forms list presets only)."""
    return _cached(
        ("models", str(workspace), "rl" if kind == "rl" else "other"),
        lambda: _model_options_uncached(workspace, kind),
    )


def _model_options_uncached(workspace: Any, kind: str) -> tuple[tuple[str, str], ...]:
    from prime_tpu.models.config import MODEL_PRESETS

    options: list[tuple[str, str]] = [(name, name) for name in sorted(MODEL_PRESETS)]
    if kind != "rl" and workspace is not None:
        import tomllib
        from pathlib import Path

        path = Path(workspace) / "configs" / "endpoints.toml"
        try:
            table = tomllib.loads(path.read_text())
        except (OSError, tomllib.TOMLDecodeError):
            table = {}
        for alias, entry in sorted(table.items()):
            if isinstance(entry, dict) and isinstance(entry.get("model"), str):
                options.append((f"{alias} (endpoint)", alias))
    return tuple(options)


def environment_options(workspace: Any = None) -> tuple[str, ...]:
    """Local environment checkouts: <workspace>/environments/*/env.toml plus
    the workspace root itself (reference _widget_local_environment_names)."""
    if workspace is None:
        return ()
    return _cached(("envs", str(workspace)), lambda: _environment_options_uncached(workspace))


def _environment_options_uncached(workspace: Any) -> tuple[str, ...]:
    import tomllib
    from pathlib import Path

    names: list[str] = []

    def name_of(env_dir: Path) -> str | None:
        try:
            data = tomllib.loads((env_dir / "env.toml").read_text())
        except (OSError, tomllib.TOMLDecodeError):
            return None
        name = data.get("environment", {}).get("name")
        return name if isinstance(name, str) and name else None

    root = Path(workspace)
    envs_dir = root / "environments"
    if envs_dir.is_dir():
        for child in sorted(envs_dir.iterdir()):
            if (child / "env.toml").exists():
                found = name_of(child)
                if found and found not in names:
                    names.append(found)
    if (root / "env.toml").exists():
        found = name_of(root)
        if found and found not in names:
            names.append(found)
    return tuple(names)


def build_form_model(normalized: NormalizedWidget, workspace: Any = None) -> FormModel:
    """Normalized configure_run args -> renderable form: per-kind field
    schedule with seeded defaults, agent config + user edits layered on top,
    model/environment selects populated from the workspace."""
    if normalized.name != "configure_run":
        raise WidgetValidationError(f"not a run form: {normalized.name!r}")
    kind = normalized.args["kind"]
    layered: dict[str, str] = {}
    for source in (normalized.args.get("config"), normalized.args.get("form_values")):
        if isinstance(source, dict):
            layered.update({str(k): str(v) for k, v in source.items()})
    if normalized.args.get("env") and "env" not in layered:
        layered["env"] = str(normalized.args["env"])

    models = model_options(workspace, kind)
    envs = environment_options(workspace)
    fields: list[FieldSpec] = []
    for name, label, input_type, default, disabled in _FORM_SCHEDULES[kind]:
        value = layered.get(name, default)
        if not value and disabled:
            continue  # a disabled field with no value carries no information
        widget = "input"
        options: tuple[tuple[str, str], ...] = ()
        if name == "model" and models:
            option_values = {v for _, v in models}
            if value and value not in option_values:
                models = ((value, value), *models)  # keep the agent's pick
            elif not value:
                value = models[0][1]
            widget, options = "select", models
        elif name == "env" and envs:
            env_opts = tuple((n, n) for n in envs)
            if value and value not in envs:
                env_opts = ((value, value), *env_opts)
            elif not value:
                value = envs[0]
            widget, options = "select", env_opts
        fields.append(
            FieldSpec(
                name=name, label=label, value=str(value), input_type=input_type,
                disabled=disabled, widget=widget, options=options,
            )
        )
    env_value = next((f.value for f in fields if f.name == "env"), "")
    env_label = (env_value or "run").rsplit("/", 1)[-1]
    title = normalized.args.get("title") or f"{_FORM_TITLES[kind]} {env_label}"
    actions = (ActionSpec("launch", "Launch", "primary"), ActionSpec("stop", "Stop"))
    schedule_names = {name for name, *_ in _FORM_SCHEDULES[kind]}
    config = normalized.args.get("config") or {}
    extras = tuple(
        (key, value) for key, value in config.items() if key not in schedule_names
    )
    return FormModel(
        kind=kind, title=title, fields=tuple(fields), actions=actions, extras=extras
    )


def parse_form_values(form: FormModel) -> tuple[dict[str, Any], list[str]]:
    """Typed parse of the form's current values: integer fields must parse
    (errors collected per field, reference parse_optional_int), 'auto' and
    blanks drop out, everything else passes as the string the user typed."""
    config: dict[str, Any] = {}
    errors: list[str] = []
    for spec in form.fields:
        value = spec.value.strip()
        if not value or value == "auto":
            continue
        if spec.input_type == "integer":
            try:
                config[spec.name] = int(value)
            except ValueError:
                errors.append(f"{spec.label}: {value!r} is not an integer")
        else:
            config[spec.name] = value
    return config, errors


def form_launch_payload(form: FormModel) -> tuple[str, dict[str, Any]]:
    """Map a parsed form onto the launch-card taxonomy (eval|train); raises
    with the collected field errors when the values don't parse."""
    config, errors = parse_form_values(form)
    if errors:
        raise WidgetValidationError("; ".join(errors))
    if not config.get("env"):
        raise WidgetValidationError("Environment is required")
    kind = {"rl": "train"}.get(form.kind, form.kind)
    if kind == "gepa":
        raise WidgetValidationError("gepa forms launch via the command line")
    # field values win over extras on key collision (can't happen today —
    # extras are by construction outside the schedule — but cheap insurance)
    return kind, {**dict(form.extras), **config}


def form_command_text(form: FormModel) -> str:
    """The CLI equivalent of the form (reference widget_command_text) — what
    the user could paste in a shell instead of arming a card."""
    config, _errors = parse_form_values(form)
    env = config.get("env", "<env>")
    model = config.get("model", "")
    if form.kind == "eval":
        parts = [f"prime eval run {env}"]
        if model:
            parts.append(f"-m {model}")
        if "limit" in config:
            parts.append(f"-n {config['limit']}")
        if "max_new_tokens" in config:
            parts.append(f"--max-new-tokens {config['max_new_tokens']}")
        return " ".join(parts)
    if form.kind == "rl":
        parts = [f"prime train request --env {env}"]
        if model:
            parts.append(f"--model {model}")
        if "max_steps" in config:
            parts.append(f"--steps {config['max_steps']}")
        return " ".join(parts)
    parts = [f"prime gepa run {env}"]
    if model:
        parts.append(f"-m {model}")
    return " ".join(parts)


def launch_card_payload(normalized: NormalizedWidget) -> tuple[str, dict[str, Any]]:
    """Card-lifecycle step: map a normalized launch_run onto the launch-card
    taxonomy (train|eval) with typed values, ready for editor.new_card /
    launch.save_card."""
    if normalized.name != "launch_run":
        raise WidgetValidationError(f"not a launch proposal: {normalized.name!r}")
    kind = {"training": "train"}.get(normalized.args["kind"], normalized.args["kind"])
    return kind, dict(normalized.args["config"])
