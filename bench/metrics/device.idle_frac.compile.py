"""Share of the traced window in which the device was idle while the
host was inside a ``serve.compile`` span of the program (tracing,
lowering or compiling any program, eager operations included), averaged
over the cell's devices (``spans.idle_split``)."""
import spans


def read(run):
    return spans.read_share(run, "compile")
