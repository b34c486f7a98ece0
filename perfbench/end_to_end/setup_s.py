"""Process start to the window's first instant: imports, JAX start, nodes,
warmup, init pods and the warm replay."""




def read(rec):
    return rec["setup_s"]
