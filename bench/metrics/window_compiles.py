"""Backend compiles inside the window (the program's
cxlsim.compile.backend counter: AOT misses, jit recompiles and eager ops on
any thread); 0 when set-up warmed every shape."""
import program_spans


def read(ctx):
    return program_spans.compiles(ctx)
