"""The one traffic generator: a traffic file's parameters -> a request
schedule.

The length model, the prompt vocabulary and the gamma arrival process are
copied from the program (``repro.data.workload.WorkloadGenerator``,
``repro.data.tokenizer.HashTokenizer``, ``repro.data.arrivals``) so that a
later change to the program cannot move the yardstick.

A request's output length comes from a latent (task, topic, verbosity)
triple with lognormal noise, capped at ``output.cap`` (LMSYS-like: mean
about 180 tokens, heavy tail).  Its prompt is the generator's question
(task phrase, topic words, filler) followed by conversation history drawn
from the generator's own vocabulary, to a length drawn lognormal and
clipped.  Arrivals are gamma intervals (shape 0.73: the paper's FabriX
fit, burstier than Poisson).

Every seed gets the same set of sizes and intervals in each segment of
the schedule (warm-up, measured window), drawn from the traffic file's
``pool_seed``; the run seed permutes them within the segment and draws the
words.  So runs with different seeds carry the same work, in another
order.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

N_SPECIAL = 8
EOS_ID = 5
#: the program's default hash-tokenizer vocabulary
VOCAB = 8192

TASKS = {
    # task -> base length, noise sigma, phrase templates
    "yesno": (8, 0.35, ["is it true that", "can you confirm whether",
                        "yes or no :"]),
    "factual": (42, 0.40, ["what is", "who invented", "when did",
                           "where can i find"]),
    "summarize": (90, 0.40, ["summarize the following text about",
                             "give me a short summary of", "tl;dr of"]),
    "explain": (220, 0.45, ["explain how", "describe the process of",
                            "why does"]),
    "code": (320, 0.50, ["write a python function that",
                         "implement an algorithm for",
                         "debug this code about"]),
    "story": (540, 0.55, ["write a story about", "compose a long essay on",
                          "write a detailed article about"]),
}
TASK_PROBS = (0.15, 0.30, 0.15, 0.20, 0.12, 0.08)

TOPICS = {
    "weather": (0.8, "rain cloud storm sunny forecast humidity wind "
                     "temperature climate snow"),
    "cooking": (0.9, "recipe bake flour oven simmer sauce garlic roast "
                     "knead season"),
    "space": (1.1, "orbit planet rocket galaxy telescope asteroid lunar "
                   "cosmic nebula gravity"),
    "history": (1.2, "empire revolution treaty dynasty medieval ancient "
                     "archive monarch war colony"),
    "software": (1.0, "compiler database thread kernel deploy container "
                      "latency cache protocol queue"),
    "biology": (1.0, "enzyme neuron protein genome cell membrane bacteria "
                     "evolution organism dna"),
    "finance": (0.7, "equity dividend portfolio inflation hedge liquidity "
                     "asset bond margin yield"),
    "music": (0.9, "melody chord rhythm tempo orchestra harmony verse "
                   "acoustic synth octave"),
}

VERBOSITY = {
    "terse": (0.45, ["briefly", "in one sentence", "very short answer :"]),
    "normal": (1.00, [""]),
    "verbose": (1.90, ["in great detail", "thoroughly and at length",
                       "step by step with examples"]),
}
VERB_PROBS = (0.25, 0.55, 0.20)

FILLER = ("please could you the a for me about regarding with respect to i "
          "want to know tell me more information on this topic thanks").split()

#: response vocabulary: opener, phase markers, closer (the predictor's
#: training answers carry fractional progress in these words)
OPENING_WORDS = "sure certainly here overview introduction begin firstly".split()
CLOSING_WORDS = ("finally conclusion summary therefore overall closing "
                 "lastly ultimately wrapping final").split()
PHASE_WORDS = [
    "opening initial premise background".split(),
    "second expanding detail elaborate".split(),
    "midpoint meanwhile further continuing".split(),
    "penultimate approaching nearing consolidating".split(),
    "finally conclusion summary closing".split(),
]
OPENING_LEN = 10
PHASE_EVERY = 8

_TASK_NAMES = list(TASKS)
_TOPIC_NAMES = list(TOPICS)
_VERB_NAMES = list(VERBOSITY)
#: words conversation history is drawn from
HISTORY_WORDS = sorted({w for _, words in TOPICS.values()
                        for w in words.split()} | set(FILLER)
                       | set(OPENING_WORDS) | set(CLOSING_WORDS))


def token_id(word: str) -> int:
    """FNV-1a word hash into ``[N_SPECIAL, VOCAB)`` (the program's
    ``HashTokenizer`` at its default vocabulary)."""
    h = 0xCBF29CE484222325
    for ch in word.lower().encode("utf-8"):
        h ^= ch
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return N_SPECIAL + h % (VOCAB - N_SPECIAL)


_IDS: dict = {}


def encode(words: Sequence[str]) -> List[int]:
    out = []
    for w in words:
        i = _IDS.get(w)
        if i is None:
            i = _IDS[w] = token_id(w)
        out.append(i)
    return out


@dataclass
class Request:
    """One request of a schedule; ``due`` is seconds after traffic start."""

    rid: int
    due: float
    prompt: str
    prompt_tokens: List[int]
    max_tokens: int
    #: the generator's synthetic answer (predictor training only)
    answer_tokens: List[int]


def _rng(*key: int) -> np.random.RandomState:
    """A numpy stream keyed by a tuple, never by a bare run seed, so the
    predictor's training stream and the run streams cannot coincide."""
    seq = np.random.SeedSequence([int(k) % (1 << 63) for k in key])
    return np.random.RandomState(seq.generate_state(4))


def _latents(n: int, rng: np.random.RandomState, p: dict):
    """Sizes of ``n`` requests: (task, topic, verbosity, output length,
    prompt length) arrays."""
    task = rng.choice(len(_TASK_NAMES), size=n, p=TASK_PROBS)
    topic = rng.randint(len(_TOPIC_NAMES), size=n)
    verb = rng.choice(len(_VERB_NAMES), size=n, p=VERB_PROBS)
    base = np.array([TASKS[t][0] for t in _TASK_NAMES], float)[task]
    sigma = np.array([TASKS[t][1] for t in _TASK_NAMES], float)[task]
    scale = np.array([TOPICS[t][0] for t in _TOPIC_NAMES], float)[topic]
    mult = np.array([VERBOSITY[v][0] for v in _VERB_NAMES], float)[verb]
    noise = rng.lognormal(0.0, sigma)
    out = np.clip(base * scale * mult * noise, 1, p["output"]["cap"])
    pr = p["prompt_tokens"]
    plen = np.exp(rng.normal(np.log(pr["median"]), pr["sigma"], size=n))
    plen = np.clip(np.round(plen), pr["min"], pr["max"])
    return task, topic, verb, out.astype(int), plen.astype(int)


def _question(rng, task: int, topic: int, verb: int) -> List[str]:
    phrases = TASKS[_TASK_NAMES[task]][2]
    vwords = VERBOSITY[_VERB_NAMES[verb]][1]
    twords = TOPICS[_TOPIC_NAMES[topic]][1].split()
    words = [vwords[rng.randint(len(vwords))],
             phrases[rng.randint(len(phrases))]]
    words += [twords[rng.randint(len(twords))]
              for _ in range(rng.randint(2, 5))]
    words += [FILLER[rng.randint(len(FILLER))]
              for _ in range(rng.randint(0, 6))]
    return " ".join(w for w in words if w).split()


def _answer(rng, topic: int, length: int) -> List[int]:
    """The generator's synthetic answer: opener, phase-marked topic body,
    closer, EOS (as ``WorkloadGenerator.sample_request`` builds it)."""
    twords = TOPICS[_TOPIC_NAMES[topic]][1].split()
    body = length - 1
    words = []
    for i in range(body):
        frac = i / max(body, 1)
        if i < OPENING_LEN:
            pool = OPENING_WORDS
        elif body - i <= 20:
            pool = CLOSING_WORDS
        elif i % PHASE_EVERY == 0:
            pool = PHASE_WORDS[min(int(frac * len(PHASE_WORDS)),
                                   len(PHASE_WORDS) - 1)]
        else:
            pool = twords
        words.append(pool[rng.randint(len(pool))])
    return encode(words) + [EOS_ID]


def _request(rng, rid, due, task, topic, verb, out, plen, answers):
    q = _question(rng, task, topic, verb)
    fill = max(int(plen) - len(q), 0)
    hist = rng.randint(len(HISTORY_WORDS), size=fill)
    ids = encode(q) + np.asarray(encode(HISTORY_WORDS))[hist].tolist()
    prompt = " ".join(q + [HISTORY_WORDS[i] for i in hist[:8]])
    return Request(rid=rid, due=float(due), prompt=prompt,
                   prompt_tokens=ids, max_tokens=int(out),
                   answer_tokens=_answer(rng, topic, int(out))
                   if answers else [])


def schedule(p: dict, rate: float, ends: Sequence[float], seed: int
             ) -> List[Request]:
    """Requests at mean ``rate`` req/s over consecutive segments ending at
    ``ends`` (e.g. the warm-up stretch and the measured window).

    Each segment holds ``round(rate x its length)`` requests whose sizes
    and gamma intervals come from the traffic's pool (fixed by
    ``pool_seed``), the intervals scaled to fill the segment exactly; the
    run seed only orders them and draws the words.  So every seed puts
    the same work into each segment."""
    reqs: List[Request] = []
    run = _rng(p["pool_seed"], 1, seed)
    start = 0.0
    for k, end in enumerate(ends):
        n = max(int(round(rate * (end - start))), 1)
        pool = _rng(p["pool_seed"], 0, k, n)
        task, topic, verb, out, plen = _latents(n, pool, p)
        gaps = pool.gamma(p["arrivals"]["gamma_shape"], 1.0, size=n)
        gaps *= (end - start) / gaps.sum()
        gaps = run.permutation(gaps)
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for i, j in enumerate(run.permutation(n)):
            reqs.append(_request(run, len(reqs), due[i], task[j], topic[j],
                                 verb[j], out[j], plen[j], answers=False))
        start = end
    return reqs


def training_requests(p: dict, n: int, seed: int) -> List[Request]:
    """``n`` requests with their synthetic answers, from a stream of their
    own, for training the length predictor."""
    rng = _rng(p["pool_seed"], 2, seed)
    task, topic, verb, out, plen = _latents(n, rng, p)
    return [_request(rng, i, 0.0, task[i], topic[i], verb[i], out[i],
                     plen[i], answers=True) for i in range(n)]
