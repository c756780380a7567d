"""Search layer: distance computations per query served, the sum of the
window's ``SearchResult.n_computed`` over the queries answered.  A count."""


def read(name: str, records: dict):
    if not records.get("queries") or "search_dist" not in records:
        return None
    return records["search_dist"] / records["queries"]
