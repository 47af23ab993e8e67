"""Fallback shims so test modules collect when `hypothesis` is absent.

Property tests decorated with this module's ``given`` are collected as
skip-marked placeholders instead of hard-failing at import (the runtime
image does not ship hypothesis; it stays a dev-only extra in pyproject).

Usage in a test module:

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        from _hypothesis_fallback import given, settings, st
"""
import inspect

import pytest


class _AnyStrategy:
    """Accepts any strategy constructor call; the result is never drawn."""

    def __getattr__(self, name):
        def strategy(*args, **kwargs):
            return None
        return strategy


st = _AnyStrategy()


def settings(*args, **kwargs):
    def decorate(fn):
        return fn
    return decorate


def given(*args, **kwargs):
    def decorate(fn):
        @pytest.mark.skip(reason="hypothesis not installed")
        def placeholder(*a, **k):
            pass
        placeholder.__name__ = fn.__name__
        placeholder.__doc__ = fn.__doc__
        # keep the arguments no strategy draws (positional strategies draw
        # the last ones), so that pytest.mark.parametrize can still name them
        params = list(inspect.signature(fn).parameters.values())
        params = [q for q in params[:len(params) - len(args)] if q.name not in kwargs]
        placeholder.__signature__ = inspect.Signature(params)
        return placeholder
    return decorate
