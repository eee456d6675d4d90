"""Hierarchical Scope: name -> runtime value symbol table.

Reference parity: ``paddle/fluid/framework/scope.h:41`` and
``variable.h:26``. A Variable here is a thin type-erased holder whose value
is a ``jax.Array`` (device tensor), a host ``LoDTensor``, or any Python
object (rank tables, reader state...). Child scopes serve RNN iterations and
per-device local scopes in the ParallelExecutor.

Holders are stable: ``var`` hands out the same ``ScopeVariable`` for a name
until ``erase`` drops it, and ``set_value`` writes through it. So whoever
looked a chain of scopes up once (the executor's state gather) may keep the
holders for as long as ``membership()`` reads the same.
"""

import itertools
import weakref

# every entry or exit of a name, in any scope, draws a new number: a
# plain ``+= 1`` could lose one of two racing bumps and leave a scope
# looking unchanged to a reader that came between them
_epochs = itertools.count(1)


class ScopeVariable(object):
    __slots__ = ("name", "value", "lod")

    def __init__(self, name):
        self.name = name
        self.value = None
        self.lod = None  # optional LoD metadata attached to a device array

    def get_tensor(self):
        return self.value

    def set(self, value, lod=None):
        self.value = value
        if lod is not None:
            self.lod = lod


class Scope(object):
    def __init__(self, parent=None):
        self._vars = {}
        self._parent = parent
        self._kids = []
        self._epoch = next(_epochs)
        self._names_memo = None   # (membership, frozenset of visible names)
        # executable -> what the executor's state gather found here last
        # time (executor.py); weak, so a plan goes with its executable
        self._gather_plans = weakref.WeakKeyDictionary()

    # -- scope.h API surface ------------------------------------------------
    def var(self, name):
        """Find-or-create in this scope (Scope::Var)."""
        v = self._vars.get(name)
        if v is None:
            v = ScopeVariable(name)
            self._vars[name] = v
            self._epoch = next(_epochs)
        return v

    def find_var(self, name):
        """Search this scope then ancestors (Scope::FindVar)."""
        scope = self
        while scope is not None:
            v = scope._vars.get(name)
            if v is not None:
                return v
            scope = scope._parent
        return None

    def erase(self, names):
        for n in names:
            self._vars.pop(n, None)
        self._epoch = next(_epochs)
        self._drop_gather_plans()

    def _drop_gather_plans(self):
        # a plan holds its holders, an erased one with its value: let go
        # of them here and wherever the lookup came through this scope
        self._gather_plans.clear()
        for kid in self._kids:
            kid._drop_gather_plans()

    def membership(self):
        """Changes whenever a name enters or leaves this scope or one of
        its ancestors, and at no other time: what a cached lookup through
        the chain is valid against."""
        epochs = []
        scope = self
        while scope is not None:
            epochs.append(scope._epoch)
            scope = scope._parent
        return tuple(epochs)

    def visible_names(self):
        """frozenset of every name ``find_var`` can reach from here; the
        same object (its hash kept) until ``membership()`` changes."""
        membership = self.membership()
        memo = self._names_memo
        if memo is None or memo[0] != membership:
            names = set()
            scope = self
            while scope is not None:
                names.update(scope._vars)
                scope = scope._parent
            memo = self._names_memo = (membership, frozenset(names))
        return memo[1]

    def new_scope(self):
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids = []

    def local_var_names(self):
        return list(self._vars)

    # -- convenience --------------------------------------------------------
    def set_value(self, name, value, lod=None):
        self.var(name).set(value, lod=lod)

    def get_value(self, name):
        v = self.find_var(name)
        return None if v is None else v.value

    def has(self, name):
        return self.find_var(name) is not None
