"""Times the client's own watch expired (410) and it had to relist, whole run."""




def read(rec):
    return float(rec["client"]["expired"])
