"""Task pipelines (L6): SSD detection, DeepSpeech2 ASR, fraud detection,
plus the column-pipeline abstraction and evaluation machinery."""

from analytics_zoo_tpu.pipelines.frame import (
    Bagging,
    Frame,
    FramePipeline,
    FuncTransformer,
    Stage,
    StandardScaler,
    StratifiedSampler,
    VectorAssembler,
    time_ordered_split,
)
from analytics_zoo_tpu.pipelines.evaluation import (
    CocoMeanAveragePrecision,
    DetectionResult,
    MeanAveragePrecision,
    MultiIoUResult,
    PascalVocEvaluator,
    mark_tp_fp,
    voc_ap,
)
from analytics_zoo_tpu.pipelines.voc import (
    VOC_CLASSES,
    Coco,
    PascalVoc,
    get_imdb,
    parse_voc_annotation,
    to_ssd_records,
)
from analytics_zoo_tpu.pipelines.ssd import (
    PreProcessParam,
    RecordToFeature,
    RoiImageToBatch,
    SSDMeanAveragePrecision,
    SSDPredictor,
    TrainParams,
    Validator,
    load_train_set,
    load_train_set_device,
    load_val_set,
    train_ssd,
    train_transformer,
    val_transformer,
)
from analytics_zoo_tpu.pipelines.frcnn import (
    FRCNN_BGR_MEANS,
    FrcnnPredictor,
    frcnn_serving_tiers,
)
from analytics_zoo_tpu.pipelines.fraud import (
    FraudResult,
    MLPClassifier,
    auprc,
    fraud_serving_tiers,
    precision_recall,
    run_fraud_pipeline,
)
from analytics_zoo_tpu.pipelines.recommendation import (
    make_ncf_model,
    make_wide_deep_model,
    predict_ratings,
    rating_batches,
    rec_serving_tiers,
    train_recommender,
)
from analytics_zoo_tpu.pipelines.sentiment import (
    make_sentiment_model,
    review_batches,
    sentiment_serving_tiers,
    train_sentiment,
)
from analytics_zoo_tpu.pipelines.visualizer import result_to_string, vis_detection
from analytics_zoo_tpu.pipelines.deepspeech2 import (
    DS2Param,
    DeepSpeech2Pipeline,
    ds2_serving_tiers,
    ds2_streaming_tiers,
    make_ds2_model,
)
from analytics_zoo_tpu.pipelines.lm import (
    CacheExhausted,
    LMModel,
    SessionCache,
    lm_serving_tiers,
    make_lm_model,
)

__all__ = [k for k in dir() if not k.startswith("_")]
