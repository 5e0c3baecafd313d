"""Server: programs made inside the window — `CompileCache.misses` plus
JAX's lowerings of a new program or eager operation. Must read 0: a
compile in the window is a shape the cell's warm-up missed."""


def read(rec):
    c = rec["compiles"]
    return float(c["cache_misses"] + c["lowerings"])
