"""Device layer: XLA compilations during the window's estimate calls, by
the program's compile counter (``repro.core.obs.compiles``; a load from
the persistent compilation cache is no compilation).  A count."""
import window_calls


def read(name: str, records: dict):
    return window_calls.total(records, "compiles")
