"""Base utilities: errors, env-var config, small helpers.

TPU-native analogue of the reference's `python/mxnet/base.py` and
`3rdparty/dmlc-core` (`dmlc::GetEnv`, logging->exceptions) [unverified paths,
see SURVEY.md provenance note]. There is no C ABI here: the "backend" is
JAX/XLA in-process, so errors are ordinary Python exceptions and configuration
is plain environment variables read at point of use, mirroring the reference's
``MXNET_*`` env-var convention.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Optional, Sequence

__all__ = [
    "MXNetError",
    "NotSupportedForSymbolAPI",
    "get_env",
    "env_bool",
    "env_int",
    "env_str",
    "numeric_types",
    "string_types",
    "logger",
]

logger = logging.getLogger("mxnet_tpu")

# every generated artifact (the XLA compile cache, the native IO library)
# lands under this one git-ignored directory of the checkout
GENERATED_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".mxtpu_cache")

numeric_types = (float, int)
string_types = (str,)


class MXNetError(RuntimeError):
    """Framework error type (reference: ``MXGetLastError`` -> MXNetError)."""


class NotSupportedForSymbolAPI(MXNetError):
    """Raised where the legacy symbolic API has no TPU-native equivalent."""


_ENV_REGISTRY: dict = {}


def get_env(name: str, default: Any, typ: Callable = str) -> Any:
    """Read ``MXNET_*``-style env var with a typed default.

    Analogue of ``dmlc::GetEnv`` [unverified]. Values are re-read on every
    call so tests can monkeypatch ``os.environ``.
    """
    _ENV_REGISTRY.setdefault(name, (default, typ))
    raw = os.environ.get(name)
    if raw is None:
        return default
    if typ is bool:
        return raw.lower() not in ("0", "false", "off", "")
    return typ(raw)


def env_bool(name: str, default: bool = False) -> bool:
    return get_env(name, default, bool)


def env_int(name: str, default: int = 0) -> int:
    return get_env(name, default, int)


def env_str(name: str, default: str = "") -> str:
    return get_env(name, default, str)


def list_env_registry() -> dict:
    """All env vars the framework has consulted (for docs/introspection)."""
    return dict(_ENV_REGISTRY)


def check_call(ret):  # pragma: no cover - compat shim, no C ABI exists
    """Compat no-op: the reference checked C-ABI return codes here."""
    return ret


def _as_list(obj) -> list:
    if obj is None:
        return []
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]


def classproperty(func):
    class _ClassProperty:
        def __get__(self, _obj, owner):
            return func(owner)

    return _ClassProperty()
