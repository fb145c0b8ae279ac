"""Mean time a batch, in ms, that the named spans of the program's span
ring spent OFF the CPU: their self time on the wall clock minus their
self time on the thread's CPU clock (`time.thread_time_ns`), summed:
args {"spans": [names]}.  Off the CPU inside a span of the queue's
worker is waiting for the interpreter lock, or blocked in a transfer
or a lock.  Same window as `span_self_per_batch`.  Nothing (never 0)
where that window reads nothing, or no span of these names in it
carries a CPU time (a program whose records have none)."""

from readers.span_self_per_batch import window


def read(args: dict, ctx: dict):
    w = window(ctx)
    cpu = getattr(w, "self_cpu_ns", None)
    names = [n for n in args["spans"] if n in (cpu or ())]
    if not names:
        return None
    return sum(w.self_ns[n] - cpu[n] for n in names) / w.batches / 1e6
