"""Model zoo — the reference's book/ chapters + BASELINE.json configs,
rebuilt on paddle_tpu's static-graph API (and dygraph where the reference
ships both).

Each module exposes `build_*` functions that append ops to the current
default program and return the key variables (prediction/loss/...), mirroring
how the reference's book tests compose `fluid.layers`. Training loops live in
the callers (tests, examples/, benchmark/) — the framework compiles the whole
step to one XLA executable either way.
"""

from . import fit_a_line
from . import mnist
from . import resnet
from . import vgg
from . import word2vec
from . import recommender
from . import lstm_text
from . import transformer
from . import bert
from . import gpt
from . import ernie
from . import deepfm
from . import gan
from . import detection_demo
from . import label_semantic_roles
from . import mobilenet
from . import ocr_recognition
from . import deeplab
from . import ctr_models
from . import tsm
from . import simnet
