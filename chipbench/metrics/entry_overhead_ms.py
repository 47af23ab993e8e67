"""Host time of a job outside its prefill and decode spans (making weights,
lowering and loading both programs, filling caches, gathering results),
mean per job of the window."""


def read(run):
    return 1e3 * sum(j.wall_s - j.prefill_s - j.decode_s for j in run.jobs) / len(run.jobs)
