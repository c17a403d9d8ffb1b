"""Executables built or loaded inside the window, after warm-up: the
program's ``jit.compile`` spans (JAX's backend compile, which wraps a
persistent-cache lookup) plus any ``jit.cache_load`` span that no
``jit.compile`` of its thread holds. 0 is expected: the drivers warm every
shape. A run whose tracer never watched compiles (no ``jit.watch`` instant)
reads nothing.
"""


def read(run):
    jit = [s for s in run.spans if s.name.startswith("jit.")]
    if run.window_start is None or not any(s.name == "jit.watch" for s in jit):
        return None
    inside = [s for s in jit if s.ts >= run.window_start]
    compiles = [s for s in inside if s.name == "jit.compile"]
    loads = [
        s for s in inside if s.name == "jit.cache_load"
        and not any(
            c.tid == s.tid and c.ts <= s.ts + s.dur / 2 <= c.ts + c.dur for c in compiles
        )
    ]
    return float(len(compiles) + len(loads))
