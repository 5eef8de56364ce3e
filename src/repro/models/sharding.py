"""Logical sharding-rule indirection.

Models annotate activations with *logical* names; the launch layer installs a
rule table mapping names to NamedShardings for the active mesh. Outside a mesh
context (unit tests, the single-host simulator) the rules are empty and
``shard`` is the identity.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax

_RULES: dict = {}


@contextmanager
def sharding_rules(rules: dict):
    global _RULES
    old = _RULES
    _RULES = dict(rules)
    try:
        yield
    finally:
        _RULES = old


def rule(name: str):
    """The installed NamedSharding for ``name``, or None."""
    return _RULES.get(name)


def shard(x, name: str):
    spec = _RULES.get(name)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
