"""The machine's one-minute load average at window start over the
cores this process may run on (``os.getloadavg()[0]`` ÷
``len(os.sched_getaffinity(0))``): what else the machine was doing,
this process's own set-up threads among it."""


def read(facts):
    return facts["counters"].get("host_load")
