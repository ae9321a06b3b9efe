"""Start-up: the programs jax asked its backend for before the measured
window, compiled or loaded from the persistent cache: the program's
``jit.compile`` spans that ended before it (``fleetx_tpu/obs/compiles.py``).
The counter ``fleetx_compile_programs_total`` runs on past the window (the
check after it compiles too), so this reads the spans."""


def before_window(run, *names):
    """The program's spans of these names that ended before the measured
    window, from the program's ring itself (``Run.spans`` holds the
    window's alone; ``run.py`` sizes the ring so that it drops none). None
    in a rehearsal, whose set-up seconds are a CPU's and no device metric,
    and where the ring holds no such span (a build of the program that
    records none)."""
    if run.cell.tiny:
        return None
    from fleetx_tpu.obs.tracing import get_recorder

    return [s for s in get_recorder().spans()
            if s.name in names and s.end_s <= run.window[0]] or None


def read(run):
    compiles = before_window(run, "jit.compile")
    if compiles is None:
        return None
    return float(len(compiles))
