"""Multi-build layer: distance computations per inserted row per
configuration, ``BuildCounters.total / (n x configurations)``: search,
prune and initialisation distances after ESO/EPO sharing.  A count."""


def read(name: str, records: dict):
    if not records.get("configs") or "build_dist" not in records:
        return None
    return records["build_dist"] / (records["n"] * records["configs"])
