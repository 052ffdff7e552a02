"""MEERKAT core: the paper's contribution as composable JAX modules."""
from repro.core.fl_step import (make_fl_round_step, make_fl_train_loop,
                                make_fl_train_step)
from repro.core.gradip import (gradip_matrix, gradip_trajectory,
                               pretrain_gradient_vec)
from repro.core.masks import (abstract_mask, concrete_balanced_mask_like,
                              magnitude_mask, random_mask, sensitivity_mask,
                              sensitivity_scores)
from repro.core.quantize import (IdentityCodec, IntCodec, QuantSpec,
                                 make_codec, quantize_roundtrip)
from repro.core.sampling import ClientSampler
from repro.core.seeds import round_keys, step_key
from repro.core.server import Client, CommLog, FederatedZO
from repro.core.spaces import DenseSpace, LoRASpace, MaskedSpace
from repro.core.virtual_path import (aggregate, reconstruct_delta,
                                     reconstruct_from_wire,
                                     reconstruct_grad_vecs)
from repro.core.vpcs import VPCSResult, analyze_trajectory, select_clients
from repro.core.zo import local_step, make_local_run, projected_gradient
