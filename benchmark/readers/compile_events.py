"""Programs JAX compiled, or fetched from the persistent cache, between the
start of the window and its last completion (JAX's own monitoring events);
should be 0."""


def read(run):
    return sum(run.window_start <= t <= run.window_end
               for t in run.compile_times)
