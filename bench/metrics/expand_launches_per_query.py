"""Data plane: EXPAND chunk launches per request, summed over the kernel
paths of ``Result.expand_paths`` (counters ``expand_calls_*``)."""


def read(run):
    per = [sum(v for k, v in r.counters.items()
               if k.startswith("expand_calls_")) for r in run.requests]
    if not any(per):
        return None
    return sum(per) / len(per)
