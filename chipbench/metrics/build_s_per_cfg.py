"""Estimator layer: seconds of multi-build per configuration estimated,
from ``EstimationRecord.build_seconds`` (a host clock that ends in the
counter tape's host sync)."""


def read(name: str, records: dict):
    if not records.get("configs") or "build_seconds" not in records:
        return None
    return records["build_seconds"] / records["configs"]
