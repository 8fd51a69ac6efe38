"""Partitions Algorithm 1 visits per query (SearchStats.partitions_visited)."""


def read(run):
    stats = [s for s in run.served.stats if s is not None]
    queries = sum(s.queries for s in stats)
    if not queries:
        return None
    return sum(s.partitions_visited for s in stats) / queries
