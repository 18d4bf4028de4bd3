"""GEPA passthrough bridge: endpoint/key injection + environment resolution.

Reference behavior (verifiers_bridge.py:1064 ``run_gepa_passthrough``, :823
``_add_default_inference_and_key_args``, :796 ``_collect_gepa_config_env``,
:68/:164 help rewriting): ``prime gepa run <env-or-config> [args...]`` is not
a blind exec — before the optimizer starts it

1. requires a configured API key,
2. injects the platform inference endpoint (``-b <inference_url>``) and API
   key (``PRIME_API_KEY`` in the child environment plus ``-k PRIME_API_KEY``)
   into the passthrough argv unless the caller picked their own provider /
   base URL / key var,
3. resolves the model through the first-class ``configs/endpoints.toml``
   alias table (prime_tpu.evals.endpoints — the tpu-native counterpart of
   the reference's verifiers endpoint registry),
4. resolves the target environment (local dir > installed > hub install —
   envhub.execution.resolve_environment) or, for a ``*.toml`` config target,
   pre-installs the environment named by the config's ``[env] env_id``.

The optional ``gepa`` package is only required at exec time, so every
injection/resolution path is testable without it installed.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path

DEFAULT_GEPA_MODEL = "openai/gpt-4.1-mini"
DEFAULT_ENV_DIR_PATH = "./environments"

# Public OpenAI-compatible provider endpoints (reference resolves these from
# the optional verifiers package's PROVIDER_CONFIGS; an unknown provider is
# passed through untouched for the downstream CLI to resolve)
PROVIDER_BASE_URLS = {
    "openai": "https://api.openai.com/v1",
    "openrouter": "https://openrouter.ai/api/v1",
    "together": "https://api.together.xyz/v1",
    "groq": "https://api.groq.com/openai/v1",
    "fireworks": "https://api.fireworks.ai/inference/v1",
}


class GepaBridgeError(Exception):
    """A bridge precondition failed (no key, no endpoint, bad target)."""


@dataclass
class GepaInvocation:
    """Everything needed to exec the optimizer: resolved run target, the
    passthrough argv with injected defaults, and the child environment."""

    run_target: str
    args: list[str]
    env: dict[str, str] = field(repr=False)  # carries the API key
    model: str = DEFAULT_GEPA_MODEL
    base_url: str | None = None
    resolved_env_name: str | None = None
    resolved_source: str | None = None
    warnings: tuple[str, ...] = ()


def parse_value_option(args: list[str], long_flag: str, short_flag: str | None) -> str | None:
    """``--flag value`` / ``--flag=value`` / ``-f value`` / ``-fvalue``."""
    for idx, arg in enumerate(args):
        if arg == long_flag or (short_flag and arg == short_flag):
            return args[idx + 1] if idx + 1 < len(args) else None
        if arg.startswith(f"{long_flag}="):
            return arg.split("=", 1)[1]
        if short_flag and arg.startswith(short_flag) and len(arg) > len(short_flag):
            return arg[len(short_flag):]
    return None


def is_help_request(primary_arg: str, passthrough_args: list[str]) -> bool:
    return primary_arg in ("--help", "-h") or any(
        a in ("--help", "-h") for a in passthrough_args
    )


def is_config_target(raw: str) -> bool:
    if raw.endswith(".toml"):
        return True
    path = Path(raw)
    return path.is_file() and path.suffix == ".toml"


def add_default_inference_and_key_args(
    passthrough_args: list[str], config
) -> tuple[list[str], dict[str, str], str, str | None]:
    """Inject the platform endpoint + key unless the caller chose their own.

    Precedence mirrors the reference exactly: explicit ``-b`` > ``-p``
    provider > endpoints.toml alias (returns early, argv untouched) >
    configured inference_url (appends ``-b``) > hard error. ``-k`` is
    appended only when the caller set neither a key var nor a provider.
    """
    args = list(passthrough_args)
    env = os.environ.copy()

    if not config.api_key:
        raise GepaBridgeError(
            "No API key configured. Run `prime login` or `prime config set-api-key`."
        )

    model = parse_value_option(args, "--model", "-m") or DEFAULT_GEPA_MODEL
    base = parse_value_option(args, "--api-base-url", "-b")
    provider = parse_value_option(args, "--provider", "-p")
    api_key_var = parse_value_option(args, "--api-key-var", "-k")
    if api_key_var is None:
        env["PRIME_API_KEY"] = config.api_key

    if base:
        base = base.rstrip("/")
    elif provider is not None:
        base = PROVIDER_BASE_URLS.get(provider)
    else:
        from prime_tpu.evals.endpoints import resolve_endpoint_alias

        endpoints_path = parse_value_option(args, "--endpoints-path", "-e")
        alias = resolve_endpoint_alias(model, endpoints_path)
        if alias is not None:
            # alias rides through untouched: the downstream CLI re-resolves
            # it against the same table (reference returns early here too)
            return args, env, alias.model, alias.base_url
        configured = (config.inference_url or "").strip().rstrip("/")
        if not configured:
            raise GepaBridgeError(
                "Inference URL not configured. Check `prime config view`."
            )
        base = configured
        args.extend(["-b", base])

    if api_key_var is None and provider is None:
        args.extend(["-k", "PRIME_API_KEY"])

    return args, env, model, base


def _collect_config_env(config_path: Path, fallback_env_dir: str) -> tuple[str, str] | None:
    """``[env] env_id`` (+ optional top-level ``env_dir_path``) from a GEPA
    TOML config; None when absent/malformed (reference: warn and skip)."""
    import tomllib

    try:
        raw = tomllib.loads(config_path.read_text())
    except (OSError, tomllib.TOMLDecodeError):
        return None
    env_table = raw.get("env")
    if not isinstance(env_table, dict):
        return None
    env_id = env_table.get("env_id")
    if not isinstance(env_id, str) or not env_id:
        return None
    env_dir_path = raw.get("env_dir_path")
    if not isinstance(env_dir_path, str):
        env_dir_path = fallback_env_dir
    return env_id, env_dir_path


def _resolve_env(env_ref: str, env_dir_path: str, hub_client):
    """Local ``<env_dir_path>/<name>`` checkout beats the registry/hub."""
    from prime_tpu.envhub.execution import resolve_environment

    local = Path(env_dir_path) / env_ref
    if (local / "env.toml").exists():
        return resolve_environment(str(local), hub_client=hub_client)
    return resolve_environment(env_ref, hub_client=hub_client)


def prepare_gepa_run(
    environment_or_config: str,
    passthrough_args: list[str],
    config,
    hub_client=None,
) -> GepaInvocation:
    """Full bridge: injected argv + resolved run target (reference
    run_gepa_passthrough minus the exec)."""
    args, env, model, base_url = add_default_inference_and_key_args(
        passthrough_args, config
    )
    env_dir_path = parse_value_option(args, "--env-dir-path", None) or DEFAULT_ENV_DIR_PATH

    run_target = environment_or_config
    resolved_name = resolved_source = None
    warnings: list[str] = []
    if is_config_target(environment_or_config):
        config_path = Path(environment_or_config)
        if not config_path.is_file():
            raise GepaBridgeError(f"GEPA config {config_path} does not exist")
        config_env = _collect_config_env(config_path, env_dir_path)
        if config_env is not None:
            resolved = _resolve_env(config_env[0], config_env[1], hub_client)
            resolved_name, resolved_source = resolved.name, resolved.source
        else:
            # reference behavior: warn and skip the pre-install, never
            # silently — the optimizer still gets the config verbatim
            warnings.append(
                f"could not read [env] env_id from {config_path}; "
                "skipping environment pre-install"
            )
    else:
        resolved = _resolve_env(environment_or_config, env_dir_path, hub_client)
        run_target = resolved.name
        resolved_name, resolved_source = resolved.name, resolved.source

    return GepaInvocation(
        run_target=run_target,
        args=args,
        env=env,
        model=model,
        base_url=base_url,
        resolved_env_name=resolved_name,
        resolved_source=resolved_source,
        warnings=tuple(warnings),
    )


_HELP_FOOTER = """
Prime-injected defaults:
  -b/--api-base-url   defaults to your configured inference URL
                      (`prime config view`); an endpoints.toml alias for the
                      model overrides it
  -k/--api-key-var    defaults to PRIME_API_KEY, exported to the optimizer
                      from your prime config
  -p/--provider       use a public provider endpoint instead
                      ({providers})
  --env-dir-path      where local environment checkouts live
                      (default {env_dir})

The first argument is an environment name/slug (resolved local > installed >
hub, installing on demand) or a GEPA TOML config whose [env] env_id is
pre-installed the same way.
""".rstrip()


def gepa_help_text() -> str:
    """The optimizer's own ``--help`` rewritten to the prime command name,
    plus the injected-defaults footer; a static summary when the optional
    package is absent (reference _load_help_text/_sanitize_help_text)."""
    import importlib.util
    import re
    import subprocess
    import sys

    footer = _HELP_FOOTER.format(
        providers=", ".join(sorted(PROVIDER_BASE_URLS)), env_dir=DEFAULT_ENV_DIR_PATH
    )
    if importlib.util.find_spec("gepa") is not None:
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "gepa", "--help"],
                capture_output=True, text=True, timeout=30,
            )
            if proc.returncode == 0 and proc.stdout.strip():
                text = re.sub(
                    r"(?im)^(usage:\s*)\S+", r"\1prime gepa run", proc.stdout
                )
                text = re.sub(r"python -m gepa", "prime gepa run", text)
                return text.rstrip() + "\n" + footer
        except (OSError, subprocess.TimeoutExpired):
            pass
    return (
        "Usage: prime gepa run ENV_OR_CONFIG [ARGS]...\n\n"
        "Run GEPA prompt optimization against a prime environment.\n"
        "(Install the optional `gepa` package for the full option list.)\n"
        + footer
    )
