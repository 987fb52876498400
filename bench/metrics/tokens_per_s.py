"""Prompt tokens prefilled plus output tokens delivered inside the window,
over the window, of every request (those of the pre-roll too).  A prompt
counts once its first token has reached the host inside the window."""


def read(run):
    start, end = run.t0, run.t0 + run.seconds
    prompt = sum(s.prompt_len for s in run.stamps.values()
                 if s.tokens and start <= s.tokens[0] <= end)
    out = sum(1 for s in run.stamps.values() for t in s.tokens
              if start <= t <= end)
    return (prompt + out) / run.seconds
