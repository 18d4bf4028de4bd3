"""Test harness config.

- Forces JAX onto a virtual 8-device CPU mesh (set BEFORE any jax import) so
  multi-chip sharding logic is testable without TPU hardware (SURVEY.md §4).
- Isolates HOME / PRIME_CONFIG_DIR per test so no test touches ~/.prime
  (mirrors the reference's HOME->tmp_path isolation, tests/test_pods_create.py).
- Provides the anyio backend fixture so async tests run under pytest without
  pytest-asyncio.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# always the CPU: libtpu is installed in the test environment and would
# otherwise look for a chip that is not there
os.environ["JAX_PLATFORMS"] = "cpu"
# hermetic: tests (and the servers they spawn) neither read nor fill the
# checkout's persistent compile cache (prime_tpu/__init__.py)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# no network: a tokenizer/checkpoint name that is not a local path fails at
# once instead of after the hub client's connection retries (~50 s a lookup)
os.environ["HF_HUB_OFFLINE"] = "1"

import pytest


@pytest.fixture
def anyio_backend():
    return "asyncio"


@pytest.fixture(autouse=True)
def _isolate_config(tmp_path, monkeypatch):
    monkeypatch.setenv("PRIME_CONFIG_DIR", str(tmp_path / ".prime"))
    monkeypatch.setenv("PRIME_DISABLE_VERSION_CHECK", "1")  # no network nag in tests
    monkeypatch.delenv("PRIME_API_KEY", raising=False)
    monkeypatch.delenv("PRIME_TEAM_ID", raising=False)
    monkeypatch.delenv("PRIME_BASE_URL", raising=False)
    monkeypatch.delenv("PRIME_CONTEXT", raising=False)
    yield
