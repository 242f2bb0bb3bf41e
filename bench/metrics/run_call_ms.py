"""Mean host time of one ``executor.run`` call in the window, up to its
return: value packing, transfers and the enqueue (harness span
``bench.run_call``)."""


def read(rec):
    d = [t1 - t0 for n, t0, t1 in rec["harness_spans"]
         if n == "bench.run_call" and t0 >= rec["t0"]]
    return sum(d) / len(d) * 1e3 if d else None
