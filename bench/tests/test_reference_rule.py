"""The training rule of ``bench/reference/lm.py`` with the dense decoder's
model module gives, on the small LM cells, the answers the reference gave
before the rule and the model were two modules: every loss and every
leaf's norm of the first gradient and of the change, equal to the last
bit."""

import pytest

from bench.archs import dense_decoder
from bench.drivers import lm_train
from bench.reference import lm as ref_lm
from bench.tests import tiny
from bench.traffic import tokens as tok

SEED = 7
# ref_lm.Run(model, job, seeds, shards).answers(check_steps) of each small
# cell on SEED, from the one-module reference
PARENT = {
    "lm.danube2.dpsvrg": {
        "loss": [5.817873001098633, 5.911222457885742, 6.010336875915527,
                 5.968845367431641, 5.746965408325195],
        "grad": {
            "embed": 1.6721928119659424,
            "final_norm": 0.17715604603290558,
            "layers/0/norm1": 0.19680404663085938,
            "layers/0/norm2": 0.15128163993358612,
            "layers/0/w_down": 1.1897926330566406,
            "layers/0/w_gate": 0.8603819608688354,
            "layers/0/w_up": 0.9179826974868774,
            "layers/0/wk": 0.7833725810050964,
            "layers/0/wo": 1.4270282983779907,
            "layers/0/wq": 0.8343205451965332,
            "layers/0/wv": 1.426282286643982,
            "layers/1/norm1": 0.09851106256246567,
            "layers/1/norm2": 0.08881497383117676,
            "layers/1/w_down": 0.7404772043228149,
            "layers/1/w_gate": 0.5405505299568176,
            "layers/1/w_up": 0.5175492763519287,
            "layers/1/wk": 0.23077361285686493,
            "layers/1/wo": 0.7966706156730652,
            "layers/1/wq": 0.24453555047512054,
            "layers/1/wv": 0.8256478905677795,
            "lm_head": 1.0554803609848022},
        "change": {
            "embed": 0.299198716878891,
            "final_norm": 0.039153069257736206,
            "layers/0/norm1": 0.03454849123954773,
            "layers/0/norm2": 0.027736002579331398,
            "layers/0/w_down": 0.2166791707277298,
            "layers/0/w_gate": 0.15424072742462158,
            "layers/0/w_up": 0.16453257203102112,
            "layers/0/wk": 0.13866958022117615,
            "layers/0/wo": 0.2524676024913788,
            "layers/0/wq": 0.1493983417749405,
            "layers/0/wv": 0.2554701268672943,
            "layers/1/norm1": 0.019290169700980186,
            "layers/1/norm2": 0.01579062081873417,
            "layers/1/w_down": 0.13583064079284668,
            "layers/1/w_gate": 0.0990285575389862,
            "layers/1/w_up": 0.0959826409816742,
            "layers/1/wk": 0.040265168994665146,
            "layers/1/wo": 0.14483925700187683,
            "layers/1/wq": 0.04366973042488098,
            "layers/1/wv": 0.15562276542186737,
            "lm_head": 0.19302748143672943},
    },
    "lm.danube2.dspg": {
        "loss": [5.825396537780762, 5.898604869842529, 5.764212608337402,
                 5.861347198486328, 5.989238739013672],
        "grad": {
            "embed": 1.4192019701004028,
            "final_norm": 0.1679716557264328,
            "layers/0/norm1": 0.1830587536096573,
            "layers/0/norm2": 0.12040923535823822,
            "layers/0/w_down": 1.0671080350875854,
            "layers/0/w_gate": 0.7422692775726318,
            "layers/0/w_up": 0.7669770121574402,
            "layers/0/wk": 0.6609317064285278,
            "layers/0/wo": 1.1963244676589966,
            "layers/0/wq": 0.7373243570327759,
            "layers/0/wv": 1.1712812185287476,
            "layers/1/norm1": 0.09596814215183258,
            "layers/1/norm2": 0.07574910670518875,
            "layers/1/w_down": 0.6842719912528992,
            "layers/1/w_gate": 0.4707726240158081,
            "layers/1/w_up": 0.4948624074459076,
            "layers/1/wk": 0.18299366533756256,
            "layers/1/wo": 0.8523224592208862,
            "layers/1/wq": 0.18164800107479095,
            "layers/1/wv": 0.7920965552330017,
            "lm_head": 1.1350737810134888},
        "change": {
            "embed": 0.12970665097236633,
            "final_norm": 0.02303374372422695,
            "layers/0/norm1": 0.016470950096845627,
            "layers/0/norm2": 0.011289726942777634,
            "layers/0/w_down": 0.10213539749383926,
            "layers/0/w_gate": 0.07515468448400497,
            "layers/0/w_up": 0.0733918845653534,
            "layers/0/wk": 0.055243682116270065,
            "layers/0/wo": 0.12175771594047546,
            "layers/0/wq": 0.060057833790779114,
            "layers/0/wv": 0.11147863417863846,
            "layers/1/norm1": 0.011340475641191006,
            "layers/1/norm2": 0.007193718571215868,
            "layers/1/w_down": 0.061207063496112823,
            "layers/1/w_gate": 0.040983665734529495,
            "layers/1/w_up": 0.043686892837285995,
            "layers/1/wk": 0.01739758625626564,
            "layers/1/wo": 0.08130980283021927,
            "layers/1/wq": 0.01854247786104679,
            "layers/1/wv": 0.08129873871803284,
            "lm_head": 0.09861428290605545},
    },
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_rule_with_the_dense_model_gives_the_parents_answers(name):
    _, workload, config = tiny.cell(name)
    job = workload["job"]
    setup = lm_train.Setup.build(config, job, SEED)
    run = ref_lm.Run(dense_decoder.reference, config["model"], job,
                     setup.seeds, tok.node_shards(setup.stream, job["nodes"]))
    assert run.answers(setup.check_steps) == PARENT[name]
