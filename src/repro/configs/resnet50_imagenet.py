"""ResNet-50 v1.5 at its published sizes: He et al. 2016 (arXiv
1512.03385), Table 1, "50-layer", with the stride-2 of each downsampling
block on its 3x3 conv, the model of MLPerf Inference's image
classification benchmark (github.com/mlcommons/inference,
``vision/classification_and_detection``) and of torchvision's
``resnet50``. 224x224x3 input, a 7x7/2 stem conv to 64 channels and a
3x3/2 max-pool, 3/4/6/3 bottleneck blocks of widths 64/128/256/512
(outputs 256/512/1024/2048), global average pool, 1000 classes: 53
convs, 25,557,032 parameters.

One departure: every conv and the max-pool pad "SAME" (TensorFlow's
rule, the extra pad row and column at the bottom and right), where
torchvision pads ``(k - 1) // 2`` on each side; a stride-2 window sits
one pixel lower and further right, with the same sizes and work."""
from ..models.cnn import ResNetConfig

CONFIG = ResNetConfig(stages=(3, 4, 6, 3), widths=(64, 128, 256, 512),
                      num_classes=1000, in_channels=3, image_size=224,
                      block="bottleneck", stem="imagenet")
