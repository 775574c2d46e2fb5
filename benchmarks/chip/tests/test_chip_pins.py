"""Pins taken before each architecture moved into a module of its own
(``arch/``): the tree before that move gave these numbers from
``weights.draw``, ``work`` and ``reference.token_gaps``, on the CPU, for the
tiny configuration (``tiny.MODEL``) and for ``qwen3-8b.L8``'s dims.  The
weights and the counts have to come out the same to the bit, the gaps within
1e-6: every seed draws the weights it drew, and every reading is taken as it
was."""
import hashlib

import numpy as np
import pytest

import reference
import spec
import tiny
import weights as W
import work

# sha256 of each leaf's bytes (bfloat16), first 16 hex digits
WEIGHTS = {7: {'layers.k_norm': '8715925f317a9489',
               'layers.norm1': '986d7bdef5929f0a',
               'layers.norm2': '1f82f29da47e5249',
               'layers.q_norm': '4544418cb2c0e486',
               'layers.w_down': '27dda33d42c15716',
               'layers.w_gate': '9acbb61329febf7b',
               'layers.w_up': '7dfa9e3316e1ec78',
               'layers.wk': '859fad48a1a6302f',
               'layers.wo': '0235830056bc493f',
               'layers.wq': '8c51bdf224547df8',
               'layers.wv': 'c57adfc34f21c8c8',
               'embed': '6fba2dd13a983010',
               'final_norm': 'ea18324b92795c7d',
               'head': 'd47543ea2cf21170',
               'w_reduce': '2aff0a44c58a1e34',
               'w_restore': '5ba67fadad2e659c'},
           8589934597: {'layers.k_norm': 'f9fa6ecb7590af3a',
                        'layers.norm1': 'f5c0b619e4f520f8',
                        'layers.norm2': '494a587766b2da5d',
                        'layers.q_norm': '66e120515ceb7cfb',
                        'layers.w_down': '99e342dbaf002d44',
                        'layers.w_gate': 'c4eafbe0ce0ba27a',
                        'layers.w_up': 'c3050514154f83d1',
                        'layers.wk': '5f829d7f9c2b397d',
                        'layers.wo': '9b1914bd799a4b7c',
                        'layers.wq': '596166ef13d7f847',
                        'layers.wv': '95a90229458d0771',
                        'embed': '2063568707f07e74',
                        'final_norm': '42eac9dac50f0e2f',
                        'head': '8045f6ef65dd2753',
                        'w_reduce': 'b8ed5479b6e2f3e4',
                        'w_restore': '26d9d5c4e7dc2025'}}

# qwen3-8b.L8 at three lengths / positions
WORK = {'edge_prefill': {1: 772833280, 899: 708003954688, 3756: 3133838131200},
        'cloud_prefill': {1: 3561062400,
                          899: 2123371184128,
                          3756: 9394882150400},
        'edge_step': {1: 772866048, 899: 802291712, 3756: 895909888},
        'cloud_step': {1: 3561160704, 899: 3649437696, 3756: 3930292224},
        'decode_token': {1: 4334026752, 899: 4451729408, 3756: 4826202112}}
LAYER_PARAMS = 192937984

# one sequence, on the tiny configuration, seed 2**31 + 7
PROMPT = [68, 65, 408, 255, 302, 307, 364, 14, 248, 75, 205, 475, 280, 36, 277,
          66, 386, 485, 501, 318, 444, 188, 74, 261, 227, 339, 509, 140, 438,
          70, 178, 403, 126, 343, 235, 262, 481, 418, 429, 281]
SERVED = [502, 502, 69, 104, 157, 283, 421, 247, 503, 180, 475, 302]
GAPS = {False: [2.4666762351989746, 4.155475199222565, 3.242024466395378,
                 4.362597465515137, 3.1700665950775146, 1.8875163793563843,
                 1.3696075677871704, 2.7084176540374756, 1.9170458316802979,
                 2.763881951570511, 2.6658531948924065, 3.328502058982849],
        True: [0.0, 0.0, 0.0, 0.0, 0.0, 1.1832890510559082,
                0.02979421615600586, 0.0, 0.0, 0.0, 0.7102643251419067,
                0.0]}


def digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("seed", sorted(WEIGHTS))
def test_weights_as_drawn_before(seed):
    w = W.draw(seed, tiny.MODEL)
    got = {f"layers.{n}": digest(a) for n, a in w["layers"].items()}
    got.update({n: digest(w[n]) for n in W.global_leaves(tiny.MODEL)})
    assert got == WEIGHTS[seed]


@pytest.mark.parametrize("count", sorted(WORK))
def test_counts_as_before(count):
    m = spec.load_cell("qwen3-8b.L8.stream-chat-wifi").model
    assert {n: getattr(work, count)(m, n) for n in WORK[count]} == \
        WORK[count]
    assert work.layer_params(m) == LAYER_PARAMS


@pytest.mark.parametrize("control", [False, True])
def test_gaps_as_before(control):
    seq = (np.asarray(PROMPT, np.int32), np.asarray(SERVED, np.int32))
    (gaps,) = reference.token_gaps(2 ** 31 + 7, tiny.MODEL, [seq],
                                   control=control)
    np.testing.assert_allclose(gaps, GAPS[control], rtol=0, atol=1e-6)
