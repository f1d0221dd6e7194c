"""The JAX/XLA crypto backend (`bccsp/tpu`).

Importing any module of this package fixes where the process keeps
JAX's persistent compilation cache, before anything here can compile:
each kernel shape takes tens of seconds to build on a v5e (PERF.md
"Bring-up"), and every peer, bench and smoke process would otherwise
pay that again.

The rule, in this one place: where `JAX_COMPILATION_CACHE_DIR` is set,
JAX already reads it and nothing is touched; otherwise the cache goes
to `.jax_cache` at the root of this checkout.  A cache that moves
between runs never hits, so the path is fixed: never a temporary name,
a pid or a time.  A process held to the CPU (`JAX_PLATFORMS=cpu`: the
test suite and every test child) gets no default cache: it compiles for
the host, XLA:CPU logs error-level machine-feature warnings at every
cached load, and a host entry carried to another machine can SIGILL.
"""

from __future__ import annotations

import os

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def compile_cache_dir() -> str:
    """Where compiled programs persist: the environment's directory,
    else the fixed one inside this checkout."""
    return os.environ.get(COMPILE_CACHE_ENV) or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def _place_compile_cache() -> None:
    if os.environ.get(COMPILE_CACHE_ENV):
        return
    import jax

    if (jax.config.jax_platforms or "").strip().lower() == "cpu":
        return
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())


_place_compile_cache()


def named_jit(fn, name: str):
    """`fn` jitted under a stable name, so that a device trace can be
    read after a refactor: the XLA module is `jit_<name>` and every
    operation's `op_name` begins `jit(<name>)/<name>/`.  A bare
    `jax.jit` of a `pallas_call` or a closure is `jit_wrapped` or
    `jit__lambda_` to every reader of the trace."""
    import jax

    def call(*args):
        with jax.named_scope(name):
            return fn(*args)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call)
