"""The process's peak resident set (ru_maxrss) over set-up and the window,
in GB: a host that runs out of memory anywhere ends the job."""


def read(rec):
    return rec.host_rss_bytes / 1e9
