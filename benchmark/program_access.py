"""The two places where the benchmark has to reach behind the program's public
entry points, kept in one file so that a later PR that gives the program a
public way (PERF.md, Open questions) has one file's callers to move.

Nothing public hands out the executable the executor compiled for a program,
and XLA's `memory_analysis()` of that executable is the only trustworthy
account of what a step needs in HBM on this runtime."""
from __future__ import annotations


def compiled_step(exe, program, scope=None, last_feed=None):
    """The executable behind `exe.run(program, ...)`: the executor's one
    cached step for a plain Program, or the CompiledProgram's jitted step
    lowered again on the live state (a cache hit)."""
    main = getattr(program, "_program", None)
    if main is None:                              # a plain Program
        steps = [fn for key, fn in exe._cache.items()
                 if key[0] == id(program)]
        if len(steps) != 1:
            raise RuntimeError(f"expected one compiled step for the program, "
                               f"found {len(steps)}")
        if steps[0]._compiled is not None:
            return steps[0]._compiled
        # a program with a giant state leaf (the 8 GiB table) runs through
        # the plain jit by design: lower it again on the live shapes (a hit
        # of the compile cache)
        import jax
        state = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for n in exe._state_names(program, scope)
                 for v in [scope.find_var(n)]}
        return steps[0]._plain.lower(
            state, last_feed, scope.find_var("@RNG_STATE@")).compile()
    fns = list(program._cache.values())
    if len(fns) != 1:
        raise RuntimeError(f"expected one compiled mesh step, found {len(fns)}")
    state = {v.name: scope.find_var(v.name) for v in main.list_vars()
             if v.persistable and scope.has_var(v.name)}
    return fns[0].lower(state, last_feed,
                        scope.find_var("@RNG_STATE@")).compile()


def memory_of(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "mosaic_calls": compiled.as_text().count("tpu_custom_call")}
