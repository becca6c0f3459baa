"""Rows of the final group table a collect: the counter ``aggGroupRows``
of ``last_query_metrics`` (the rows a final or complete aggregate hands on,
summed over its partitions) of each query's last warm collect, averaged
over the cell's queries.  Nothing where the program has no such counter."""


def read(run):
    counts = [(run["query_metrics"].get(q) or {}).get("aggGroupRows")
              for q in run["cell"]["queries"]]
    if any(c is None for c in counts) or not counts:
        return None
    return sum(counts) / len(counts)
