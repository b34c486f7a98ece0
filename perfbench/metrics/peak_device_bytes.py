"""memory_stats()[peak_bytes_in_use] of the fullest chip after the window; None
where the backend reports none."""




def read(rec):
    p = rec["peak_device_bytes"]
    return float(p) if p else None
