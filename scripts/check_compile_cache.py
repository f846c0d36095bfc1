#!/usr/bin/env python
"""Assert JAX's persistent compilation cache actually persists compiles.

The serving warmup (ddim_cold_tpu/serve/warmup.py) leans on the cache to
make a process restart compile-free — this check proves the wiring on the
running JAX, end to end, in the directory the program itself uses
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache`` or
the directory given as the argument):

1. ``enable_compile_cache`` turns the cache on;
2. a jitted function compiles → the directory must hold an entry;
3. the in-memory jit cache is cleared and the SAME function recompiles →
   the directory must NOT gain another entry (the disk hit served it).

Exit codes: 0 = verified, 1 = the cache directory was not used.

Usage: ``python scripts/check_compile_cache.py [cache_dir]``
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _entries(path):
    names = []
    for root, _, files in os.walk(path):
        names += [os.path.join(root, f) for f in files]
    return sorted(names)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv

    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.utils.platform import enable_compile_cache

    active = enable_compile_cache(os.path.abspath(argv[0]) if argv else None)
    # production keeps a 0.5 s floor so trivial compiles don't churn the
    # disk; the check's probe compile IS trivial, so the floor must drop
    # or the assertion below would test the floor, not the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    @jax.jit
    def probe(x):
        return jnp.sin(x) * jnp.arange(x.shape[0], dtype=x.dtype) + 3.0

    probe(jnp.ones((16,))).block_until_ready()
    after_first = _entries(active)
    if not after_first:
        print(f"FAIL: compile left no entry under {active} — the "
              "persistent cache is configured but unused")
        return 1
    print(f"ok: after the first compile {active} holds {len(after_first)} "
          f"entr{'y' if len(after_first) == 1 else 'ies'}")

    probe.clear_cache()  # drop the in-memory executable, keep the disk
    probe(jnp.ones((16,))).block_until_ready()
    after_second = _entries(active)
    if after_second != after_first:
        print("FAIL: recompile after clear_cache changed the cache dir "
              f"({len(after_first)} → {len(after_second)} entries) — "
              "the disk entry was not reused")
        return 1
    print("ok: recompile after clear_cache reused the disk entry "
          "(no new files)")
    print(f"PASS: persistent compilation cache verified at {active}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
