"""Estimator layer: seconds of recall/QPS sweep per configuration
estimated, from ``EstimationRecord.eval_seconds`` (each point ends in
``block_until_ready``)."""


def read(name: str, records: dict):
    if not records.get("configs") or "eval_seconds" not in records:
        return None
    return records["eval_seconds"] / records["configs"]
