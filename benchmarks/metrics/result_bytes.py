"""Bytes of the answer as fetched from the device a collect: the counter
``d2h_bytes`` of ``last_query_metrics`` (the padded arrays the terminal
fetch copies, a string's matrix included) of each query's last warm
collect, averaged over the cell's queries.  Nothing where the program has
no such counter."""


def read(run):
    counts = [(run["query_metrics"].get(q) or {}).get("d2h_bytes")
              for q in run["cell"]["queries"]]
    if any(c is None for c in counts) or not counts:
        return None
    return sum(counts) / len(counts)
