"""Programs the engine got ready for the first time between the window's
start and its end: growth of ``engine._compiled``. Must be 0 (``correct``)."""


def read(run: dict):
    return run.get("compiles_in_window")
