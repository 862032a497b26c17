"""launch.compile_s: seconds the compile cache spent compiling wave
shapes new to the process, per launch of the window
(CompileCache.stats["compile_s"], summed over compiling threads)."""


def read(obs):
    launches = obs.get("launches")
    if not launches:
        return None
    return sum(r["compile_s"] for r in launches) / len(launches)
