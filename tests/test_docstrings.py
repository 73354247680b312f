"""Docstring coverage for the public comm + machine surface.

The hetero PR grows the public API (device geometry, rails, staged
strategies); this test makes "ships documented" a contract, not a habit:
every public callable defined in the :mod:`repro.comm` modules and in
:mod:`repro.net.machine` must carry a docstring that *mentions each of its
parameters by name* — a reader should never have to reverse-engineer an
argument from the implementation.

Scope rules: public = not underscore-prefixed and defined in the module
under test (re-exports are covered where they are defined).  For classes,
the class itself must have a docstring and each public method (including
classmethods/staticmethods) is checked like a function; properties,
dataclass machinery and dunders are skipped.  A parameter counts as
mentioned if its name appears as a word anywhere in the callable's — or,
for ``__init__``-less dataclasses, the owning class's — docstring.
"""
import inspect
import re

import pytest

import repro.comm.delta
import repro.comm.faults
import repro.comm.guard
import repro.comm.health
import repro.comm.obs
import repro.comm.phase
import repro.comm.primitives
import repro.comm.stack
import repro.comm.strategies
import repro.exec.calibrate
import repro.exec.lower
import repro.exec.measure
import repro.exec.plan
import repro.exec.presets
import repro.exec.reference
import repro.net.machine
import repro.serve.admission
import repro.serve.cache
import repro.serve.strategy
import repro.workloads.moe
import repro.workloads.pipe
import repro.workloads.registry
import repro.workloads.tp

MODULES = [repro.comm.phase, repro.comm.primitives, repro.comm.stack,
           repro.comm.delta, repro.comm.strategies, repro.net.machine,
           repro.workloads.moe, repro.workloads.tp, repro.workloads.pipe,
           repro.workloads.registry, repro.comm.guard, repro.comm.faults,
           repro.comm.health, repro.comm.obs, repro.serve.strategy,
           repro.serve.admission, repro.serve.cache,
           repro.exec.plan, repro.exec.reference, repro.exec.lower,
           repro.exec.measure, repro.exec.calibrate, repro.exec.presets]

#: Parameter names that need no mention: conventions, not API.
IGNORED_PARAMS = {"self", "cls", "args", "kwargs", "kw"}


def _methods_of(klass):
    for name, member in vars(klass).items():
        if name.startswith("_"):
            continue
        if isinstance(member, (classmethod, staticmethod)):
            yield name, member.__func__, klass
        elif inspect.isfunction(member):
            yield name, member, klass


def _public_callables():
    out = []
    for mod in MODULES:
        for name, obj in sorted(vars(mod).items()):
            if name.startswith("_") or getattr(obj, "__module__",
                                               None) != mod.__name__:
                continue
            if inspect.isfunction(obj):
                out.append((f"{mod.__name__}.{name}", obj, None))
            elif inspect.isclass(obj):
                out.append((f"{mod.__name__}.{name}", obj, None))
                for mname, fn, klass in _methods_of(obj):
                    out.append((f"{mod.__name__}.{name}.{mname}", fn, klass))
    return out


CALLABLES = _public_callables()
assert len(CALLABLES) > 40            # the surface is real, not a no-op scan


def _mentions(doc: str, param: str) -> bool:
    return re.search(rf"\b{re.escape(param)}\b", doc) is not None


@pytest.mark.parametrize("qualname, obj, klass",
                         CALLABLES, ids=[c[0] for c in CALLABLES])
def test_public_callable_documents_its_parameters(qualname, obj, klass):
    doc = inspect.getdoc(obj)
    assert doc, f"{qualname} has no docstring"
    if inspect.isclass(obj):
        return                        # methods are checked individually
    try:
        sig = inspect.signature(obj)
    except (TypeError, ValueError):   # builtins/descriptors: nothing to check
        return
    class_doc = inspect.getdoc(klass) or "" if klass is not None else ""
    missing = [p for p in sig.parameters
               if p not in IGNORED_PARAMS
               and not _mentions(doc, p) and not _mentions(class_doc, p)]
    assert not missing, \
        f"{qualname} docstring does not mention parameter(s) {missing}"
