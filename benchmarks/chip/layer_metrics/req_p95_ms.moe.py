"""Split serving under load, Mellum split serving: the 95th percentile
of completion minus due time (queueing included), over the requests due
and completed inside the traced part. Stopping the profiler holds the
loop for seconds, so the rest of a traced window says nothing of the
queue."""


def read(run):
    return run.percentile("req_latency_ms", 95)
