"""Backend compiles counted inside the window; has to read 0."""
from pb.readers import counter_in_window


def read(run):
    return counter_in_window(run, "compile.traces.backend_compile")
