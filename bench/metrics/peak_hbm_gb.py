"""Peak device memory a step holds, in GB (1e9 bytes), on the fullest
chip: the arrays at their peak (``memory_stats()["peak_bytes_in_use"]``
after the window) plus the compiled step's temporaries (the compiler's
memory analysis). The TPU runtime's counter leaves the executable's
temporaries out, so it alone reads less than the step's largest buffer."""


def read(rec):
    peak, temp = rec.get("memory_peak_bytes"), rec.get("step_temp_bytes")
    if not peak or temp is None:
        return None
    return (peak + temp) / 1e9
