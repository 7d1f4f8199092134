"""Seconds the program cache spent lowering and compiling (or loading from
the persistent cache) in set-up: `lower_s + compile_s` over its programs."""


def read(ctx):
    progs = ctx["programs"]
    if not progs:
        return None
    return sum(p.get("lower_s", 0.0) + p.get("compile_s", 0.0)
               for p in progs)
