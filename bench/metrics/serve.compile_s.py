"""serve.compile_s: seconds the compile cache spent compiling inside the
window (CompileCache.stats["compile_s"]); 0 when set-up warmed every
shape the traffic reached."""


def read(obs):
    w = obs.get("window")
    return None if not w else w["compile_s"]
