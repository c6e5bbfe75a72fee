"""setup_s: seconds from the process's start to the window's opening:
imports, data, the program's graph and session or server, warm-up and
every compile it causes."""


def read(rec):
    return rec["setup_s"]
