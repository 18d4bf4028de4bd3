"""Eval endpoint aliasing and launch preflights.

Reference behavior (verifiers_bridge.py:823-897): before an eval launches
rollouts, the model argument is resolved through a ``configs/endpoints.toml``
alias table, the model id is validated against the inference API, and a
1-token completion probes billing — so a typo'd model 404s and an empty
wallet 402s in seconds, not minutes into a provisioned run.

TPU-native shape: the alias table is first-class TOML (one table per alias),
the preflights ride the existing ``InferenceClient``, and an alias carrying a
``base_url`` makes the eval *inference-backed* — the runner generates through
the remote OpenAI-compatible endpoint via :class:`ApiGenerator` instead of
loading weights locally, which is how verifiers-style endpoint evals work.

Alias file format::

    [smoke-model]                      # `prime eval run env -m smoke-model`
    model = "llama3.2-1b"              # what the alias resolves to
    base_url = "https://..."           # optional: OpenAI-compatible endpoint
"""

from __future__ import annotations

import tomllib
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

DEFAULT_ENDPOINTS_PATH = "configs/endpoints.toml"
# preflights must fail fast — generation timeouts (600 s) are far too long
# for a 1-token probe (reference EVAL_PREFLIGHT_TIMEOUT)
PREFLIGHT_TIMEOUT_S = 30.0


class EvalPreflightError(Exception):
    """A preflight failed hard (invalid model / payment required)."""


@dataclass(frozen=True)
class EndpointResolution:
    model: str
    base_url: str | None = None


def resolve_endpoint_alias(
    model: str, endpoints_path: str | Path | None = None
) -> EndpointResolution | None:
    """Resolve ``model`` through the endpoints alias table.

    Returns None when there is no table file or no matching alias (the model
    string then means a preset/checkpoint as usual). A malformed table, a
    matching entry without a usable ``model`` key, or an EXPLICITLY passed
    path that doesn't exist raises — a typo'd alias file or --endpoints-path
    must not silently fall through to "treat the alias as a model".
    """
    explicit = endpoints_path is not None
    path = Path(endpoints_path or DEFAULT_ENDPOINTS_PATH)
    if not path.is_file():
        if explicit:
            raise EvalPreflightError(f"Endpoints file {path} does not exist")
        return None
    try:
        table = tomllib.loads(path.read_text())
    except tomllib.TOMLDecodeError as e:
        raise EvalPreflightError(f"Malformed endpoints file {path}: {e}") from None
    entry = table.get(model)
    if entry is None:
        return None
    if not isinstance(entry, dict) or not isinstance(entry.get("model"), str) or not entry["model"]:
        raise EvalPreflightError(
            f"Endpoints alias {model!r} in {path} must be a table with a "
            "non-empty string 'model' key"
        )
    base_url = entry.get("base_url")
    if base_url is not None and not isinstance(base_url, str):
        raise EvalPreflightError(f"Endpoints alias {model!r}: base_url must be a string")
    return EndpointResolution(
        model=entry["model"],
        base_url=base_url.rstrip("/") if base_url else None,
    )


def _preflight_client(base_url: str | None):
    import httpx

    import prime_tpu.commands._deps as deps
    from prime_tpu.api.inference import InferenceClient

    return InferenceClient(
        config=deps.build_config(),
        base_url=base_url,
        timeout=httpx.Timeout(PREFLIGHT_TIMEOUT_S, connect=10.0),
        transport=deps.transport_override,
    )


def validate_model(
    model: str, base_url: str | None = None, warn: Callable[[str], None] = lambda _m: None
) -> None:
    """Fail fast if the inference API doesn't know ``model``.

    Timeouts warn and continue (reference: some thinking models take longer
    to warm up than the preflight budget); API errors abort. NOTE:
    ``APIClient`` wraps every ``httpx.TimeoutException`` into
    ``APITimeoutError`` (core/client.py), so the timeout catch must target
    that subclass BEFORE the generic ``APIError``.
    """
    from prime_tpu.core.exceptions import APIError, APITimeoutError

    try:
        _preflight_client(base_url).retrieve_model(model)
    except APITimeoutError:
        warn(f"Timed out validating model {model!r} during eval preflight; continuing.")
    except APIError as e:
        raise EvalPreflightError(
            f"Invalid model {model!r}: {e} — see `prime inference models`"
        ) from None


def preflight_billing(
    model: str, base_url: str | None = None, warn: Callable[[str], None] = lambda _m: None
) -> None:
    """1-token completion probe: a 402 aborts before anything is launched.

    Only payment failures abort — other API errors (e.g. a model that can't
    chat) warn and let the real run produce the real error; timeouts warn
    and continue.
    """
    from prime_tpu.core.exceptions import APIError, APITimeoutError, PaymentRequiredError

    try:
        _preflight_client(base_url).chat_completion(
            model, [{"role": "user", "content": "Reply with OK."}], max_tokens=1
        )
    except APITimeoutError:
        warn(f"Timed out on the billing preflight for {model!r}; continuing.")
    except PaymentRequiredError as e:
        raise EvalPreflightError(str(e)) from None
    except APIError as e:
        warn(f"Billing preflight for {model!r} returned {e}; continuing.")


class ApiGenerator:
    """Eval generator backed by an OpenAI-compatible inference endpoint.

    The remote twin of ``JaxGenerator``: completions come from chat
    completions against ``base_url`` (or the configured inference URL), so an
    endpoints alias with a ``base_url`` evaluates a deployed model with the
    same env/scorer/results pipeline the local JAX path uses."""

    def __init__(
        self,
        model: str,
        base_url: str | None = None,
        temperature_cap: float | None = None,
    ) -> None:
        import prime_tpu.commands._deps as deps
        from prime_tpu.api.inference import InferenceClient

        self.model = model
        self.client = InferenceClient(
            config=deps.build_config(),
            base_url=base_url,
            transport=deps.transport_override,
        )
        self.temperature_cap = temperature_cap

    MAX_CONCURRENCY = 16

    def generate(
        self,
        prompts: list[str],
        max_new_tokens: int,
        temperature: float,
        top_p: float = 1.0,
        templated: bool = False,
    ) -> list[str]:
        del top_p, templated  # endpoint applies its own chat template
        from concurrent.futures import ThreadPoolExecutor

        def one(prompt: str) -> str:
            response = self.client.chat_completion(
                self.model,
                [{"role": "user", "content": prompt}],
                max_tokens=max_new_tokens,
                temperature=temperature,
            )
            choices = response.get("choices") or []
            message = (choices[0].get("message") or {}) if choices else {}
            return message.get("content") or ""

        # remote endpoints want request-level concurrency, not batching — a
        # pool the size of the batch keeps one slow generation from
        # serializing the whole run
        with ThreadPoolExecutor(max_workers=min(len(prompts), self.MAX_CONCURRENCY)) as pool:
            return list(pool.map(one, prompts))
