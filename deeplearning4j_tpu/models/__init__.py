"""Model zoo (SURVEY.md §2.5 deeplearning4j-zoo)."""

from .lenet import lenet, lenet_config  # noqa: F401
from .resnet import resnet, resnet50  # noqa: F401
from .nasnet import nasnet_mobile  # noqa: F401
from .laguna import laguna  # noqa: F401
from .kanana import kanana2  # noqa: F401
from .ouro import ouro  # noqa: F401
from .keye import keye_vl2  # noqa: F401
from .facenet import facenet_nn4_small2, inception_resnet_v1  # noqa: F401
from .zoo import (alexnet, darknet19, simple_cnn, squeezenet,  # noqa: F401
                  text_generation_lstm, tiny_yolo, unet, vgg16, vgg19,
                  xception, yolo2)
