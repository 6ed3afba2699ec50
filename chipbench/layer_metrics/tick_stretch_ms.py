"""What a prefill program on the device costs the tick dispatched behind it: median wait for the tokens (t_tok - t_disp1) of the window's ticks whose record names a prefill batch in flight (behind), less the median of those that name none.  None under 5 ticks of either kind."""
import turn_spans

NAME = "tick_stretch_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    split = turn_spans.ticks_by_behind(obs)
    if split is None or min(map(len, split)) < turn_spans.MIN_TICKS:
        return None
    behind, clear = (turn_spans.median_ms(ticks, "t_disp1", "t_tok") for ticks in split)
    return behind - clear
