"""Device layer: the share of the traced window, in %, in which no
operation ran on the chip (1 - busy / window, busy being the union of the
device-op intervals)."""


def read(name: str, records: dict):
    red = records.get("trace")
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * red["idle_share"]
