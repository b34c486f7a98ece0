"""Pods due in the window that were bound by the drain deadline, over pods due."""




def read(rec):
    due = rec["due"]
    if not due:
        return None
    return 100.0 * sum(1 for ns, name, *_ in due if (ns, name) in rec["bound"]) / len(due)
