"""Where JAX's persistent compilation cache lives — one rule for every
entry point (cli.main, benchmark/run.py, chip_smoke.py's children,
experiments/).

The directory is part of the cache key, so it must be the same from run
to run: where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself
and nothing here names a directory; where it is not, the cache goes to
one fixed git-ignored directory inside the checkout — never a temporary
name, a pid or a time. Two cases set no directory at all: the tests
switch the cache off (tests/conftest.py sets
JAX_ENABLE_COMPILATION_CACHE=false), and a run held to the CPU
(JAX_PLATFORMS=cpu) has nothing worth keeping — CPU compiles are short,
XLA:CPU warns on every entry it reloads, and an entry built on another
host's CPU may not run on this one.
"""

import os

#: frames of the Python call stack a lowered op's location keeps. One, by
#: limiting the traceback and NOT by switching
#: jax_include_full_tracebacks_in_locations off, which PR 22 did: that
#: also moves the name stack out of the op's name, and the compiled step's
#: op_name then reads "conv_general_dilated" where it should read
#: "jit(step)/jvp(conv1)/conv_general_dilated" — every jax.named_scope
#: lost on the way to the profile (PR 25, chip traces).
LOCATION_FRAMES = 1

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache():
    """Call before the first compile. Returns the directory in use, or
    None when the persistent cache is switched off."""
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    # A pallas kernel rides in its custom call as serialized MLIR WITH
    # debug locations, and by default a location holds the Python call
    # stack: the same step lowered from another entry point (the lm verb,
    # chip_smoke.py's HLO child) got another cache key, and the
    # d1024 LM step never hit (PR 22, chip runs 1-2). The innermost frame
    # is location enough.
    jax.config.update("jax_traceback_in_locations_limit", LOCATION_FRAMES)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
