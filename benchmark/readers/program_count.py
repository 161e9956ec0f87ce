"""A count the program keeps of itself, read at the end of set-up:
``params["count"]`` is ``fresh_compiles`` (``mx.compile_report()``),
``pass_sites`` (``mx.pass_report()``: sites applied over all passes) or
``xla_programs`` (programs JAX built or loaded, counted by the harness)."""


def read(params, facts):
    return facts["counts"].get(params["count"])
