"""Percent of the window's query rows that the fused loop left to the
exact brute tail (the index's ``brute_tail_queries`` counter)."""


def read(record):
    if not record.get("rows"):
        return None
    return 100.0 * record["counters"]["brute_tail_queries"] / record["rows"]
