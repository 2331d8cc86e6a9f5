"""Per collect, mean over the window: CPU seconds the process burnt,
all its threads (``time.process_time()`` at window start and after the
last collect).  Beside ``scan_decode_s`` + ``scan_stage_s``, which are
span seconds, it tells a stage that worked from one that waited: a slow
window with the same CPU seconds was kept off its cores, one with more
ran slower on them."""


def read(facts):
    return facts["counters"].get("host_cpu_s")
