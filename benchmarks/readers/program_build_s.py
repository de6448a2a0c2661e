"""Model step: seconds the engine's programs took to build, summed over
``InferenceEngine.programs()`` at the end of the run: every call in which
JAX built (trace, lower, and a compile or a load from the compile cache: a
jit key's first use, or arguments of another kind under it).  It
is the part of ``setup_s`` that the compile cache and the count of program
variants decide; a correct run builds nothing inside its window.  A program
that keeps no such table reads nothing."""


def read(ctx):
    programs = getattr(getattr(ctx, "engine", None), "programs", None)
    if programs is None:
        return None
    return sum(p["build_s"] or 0.0 for p in programs())
