"""Version-shim system — the analog of the reference's ShimLoader /
SparkShimServiceProvider pattern (``ShimLoader.scala:46-76``,
``sql-plugin-api``; SURVEY §2.11).  The reference's compatibility axis is
the Spark version; ours is the jax/jaxlib version: APIs this framework
leans on have moved between releases (``shard_map`` graduated from
``jax.experimental``, the ``jax.tree`` namespace replaced ``tree_util``
entry points).  One provider exists today (jax >= 0.6, the installed
line); an older or newer jax whose surface differs gets a provider of its
own when it is actually installed and tested.

Providers are probed in order against the running jax version; the first
match supplies the version-dependent API surface.  New jax releases get a
new provider class — nothing outside this package changes (the
parallel-world property the reference's classloader gives the JVM)."""

from __future__ import annotations

import threading
from typing import Callable, List, Optional, Tuple


def _jax_version() -> Tuple[int, ...]:
    import jax
    parts = []
    for tok in jax.__version__.split("."):
        digits = "".join(ch for ch in tok if ch.isdigit())
        if not digits:
            break
        parts.append(int(digits))
    return tuple(parts[:3])


class ShimProvider:
    """SparkShimServiceProvider analog: matches a jax version range and
    supplies the version-dependent APIs."""

    #: inclusive lower bound, exclusive upper bound (None = open)
    min_version: Tuple[int, ...] = (0,)
    max_version: Optional[Tuple[int, ...]] = None

    @classmethod
    def matches(cls, version: Tuple[int, ...]) -> bool:
        if version < cls.min_version:
            return False
        if cls.max_version is not None and version >= cls.max_version:
            return False
        return True

    # --- the shimmed API surface -------------------------------------------
    def shard_map(self) -> Callable:
        raise NotImplementedError

    def tree_map(self) -> Callable:
        raise NotImplementedError

    def tree_flatten(self) -> Callable:
        raise NotImplementedError

    def tree_unflatten(self) -> Callable:
        raise NotImplementedError

    def description(self) -> str:
        return (f"{type(self).__name__} "
                f"[{'.'.join(map(str, self.min_version))}, "
                f"{'.'.join(map(str, self.max_version)) if self.max_version else 'open'})")


class JaxModernShim(ShimProvider):
    """jax >= 0.6: top-level ``jax.shard_map`` and the ``jax.tree``
    namespace are canonical."""

    min_version = (0, 6)
    max_version = None

    def shard_map(self):
        import jax
        return jax.shard_map

    def tree_map(self):
        import jax
        return jax.tree.map

    def tree_flatten(self):
        import jax
        return jax.tree.flatten

    def tree_unflatten(self):
        import jax
        return jax.tree.unflatten


#: probe order — first match wins (ShimLoader service-provider probing)
PROVIDERS: List[type] = [JaxModernShim]

_lock = threading.Lock()
_active: Optional[ShimProvider] = None


def get_shim() -> ShimProvider:
    """The active provider for the running jax (cached; lock-free fast
    path — the wrappers sit on per-batch hot paths)."""
    global _active
    if _active is not None:
        return _active
    with _lock:
        if _active is None:
            v = _jax_version()
            for cls in PROVIDERS:
                if cls.matches(v):
                    _active = cls()
                    break
            else:
                raise RuntimeError(
                    f"no shim provider matches jax {v}; known: "
                    f"{[c.__name__ for c in PROVIDERS]}")
        return _active


def shard_map():
    return get_shim().shard_map()


def tree_map(f, *trees):
    return get_shim().tree_map()(f, *trees)


def tree_flatten(tree):
    return get_shim().tree_flatten()(tree)


def tree_unflatten(treedef, leaves):
    return get_shim().tree_unflatten()(treedef, leaves)
