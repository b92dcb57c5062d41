"""Share of the window that XLA spent getting programs: compiling them, or
loading them from the persistent cache (JAX's backend_compile_duration
events inside the window, which time both)."""


def read(run):
    return 100.0 * run.compile_s / run.window_s
