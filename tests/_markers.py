"""Shared environment-capability gates for test modules.

This lives in its own module (not conftest.py) because ``import conftest``
from a test module is ambiguous with tests/live/conftest.py.
"""

import importlib.util

import pytest

# the login handshake (commands/login.py) encrypts the API key exchange with
# an RSA keypair; containers without the cryptography wheel can't run it
requires_cryptography = pytest.mark.skipif(
    importlib.util.find_spec("cryptography") is None,
    reason="cryptography not installed in this environment (env gap)",
)
