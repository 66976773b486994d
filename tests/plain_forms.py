"""A family's plain form (``benchmarks/families/<family>.py``
``plain_loss``) as the family files' term tests call it: under ``jit``,
compiled once a configuration. Op by op it is a trace, a lowering and a
compile for every primitive, and a fresh ``jit`` a call would compile the
base configuration again for every term."""

import functools
import json

import jax

_JITTED = {}


def jitted_plain_loss(family, config):
    """``family.plain_loss(params, tokens, config)`` as a jitted function
    of ``(params, tokens)``, one a family and configuration."""
    key = (family.__name__, json.dumps(config, sort_keys=True))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(functools.partial(
            family.plain_loss, config=config))
    return _JITTED[key]
