"""Scope: hierarchical name -> runtime value map.

Reference parity: paddle/fluid/framework/scope.h:39-81 (Var / FindVar /
NewScope / DropKids). Values are jax.Arrays (device-resident), LoDTensor
wrappers, or host objects (readers, lod rank tables). Parameters and
optimizer state persist here between Executor.run calls; on TPU they stay
device-resident so steps never round-trip through host memory.
"""

from .lod_tensor import LoDTensor


class Scope:
    def __init__(self, parent=None):
        self._vars = {}
        # {a kept low-precision copy's name: the value of its master it was
        # cast from} (executor_core.refresh_kept_copies)
        self.cast_from = {}
        self.parent = parent
        self.kids = []

    def var(self, name):
        """Find-or-create in THIS scope (reference Scope::Var)."""
        if name not in self._vars:
            self._vars[name] = None
        return name

    def find_var(self, name):
        """Recursive lookup (reference Scope::FindVar). Returns value or None."""
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name):
        s = self
        while s is not None:
            if name in s._vars:
                return True
            s = s.parent
        return False

    def set_var(self, name, value):
        self._vars[name] = value

    def erase(self, name):
        self._vars.pop(name, None)

    def new_scope(self):
        kid = Scope(parent=self)
        self.kids.append(kid)
        return kid

    def drop_kids(self):
        self.kids = []

    def local_var_names(self):
        return list(self._vars.keys())

    def find_tensor(self, name):
        v = self.find_var(name)
        if isinstance(v, LoDTensor):
            return v
        return v


import threading

_global_scope = Scope()
_tls = threading.local()


def _stack():
    """Per-THREAD scope stack. A fresh thread starts at the process-wide
    global scope, so one thread's scope_guard (e.g. a pserver serving from
    its own scope) never redirects another thread's global_scope() — the
    reference gets the same isolation by passing Scope& explicitly."""
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = [_global_scope]
    return st


def global_scope():
    return _stack()[-1]


def reset_global_scope(scope=None):
    """Replace the process-wide global scope (test isolation)."""
    global _global_scope
    _global_scope = scope if scope is not None else Scope()
    _tls.stack = [_global_scope]
    return _global_scope


def _switch_scope(scope):
    _stack().append(scope)
    return scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        st = _stack()
        st.append(scope)
        try:
            yield
        finally:
            st.pop()

    return _guard()
