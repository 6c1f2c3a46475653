"""Backend compiles counted inside a window of soft trees; has to read 0
(the boundary's programs are compiled before the first fit)."""
from pb.readers import counter_in_window


def read(run):
    return counter_in_window(run, "compile.traces.backend_compile")
