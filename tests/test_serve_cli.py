"""``scripts/serve.py`` without ``--input_file``: the synthetic trace is
a function of ``--seed`` alone."""

from types import SimpleNamespace

from scripts.serve import load_trace


def test_synthetic_trace_is_the_parents():
    """The quick start (``scripts/serve.py --seed 0 --requests 12``)
    submits the prompts and lengths it always has: the literals were
    captured at PR 29, when the server still took its trace from the
    CPU benchmark that PR 31 deleted."""
    args = SimpleNamespace(
        input_file=None, seed=0, requests=12, prompt_min=8, prompt_max=48,
        max_new_tokens=64, temperature=0.0, top_k=0, top_p=0.0,
        sample_seed=0)
    trace = load_trace(args, vocab=1023)
    assert [len(p) for p, _, _ in trace] == [
        8, 31, 27, 23, 19, 29, 31, 46, 33, 32, 28, 41]
    assert [m for _, m, _ in trace] == [
        8, 7, 5, 52, 9, 14, 4, 53, 10, 9, 12, 37]
    assert trace[0][0].tolist() == [836, 764, 708, 360, 10, 724, 278, 755]
    assert all(kw == {"temperature": 0.0, "top_k": 0, "top_p": 0.0,
                      "seed": 0} for _, _, kw in trace)
