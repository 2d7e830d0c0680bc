from repro.core.spaces import (ParamSpace, loguniform, Int, LogInt, Choice,
                               CHOICE_KEY)
from repro.core.optimizer import AskTellOptimizer, Trial
from repro.core.studybank import StudyBank, StudyLedger
from repro.core.tuner import Tuner, TunerResults
from repro.core.async_tuner import AsyncTuner

__all__ = ["ParamSpace", "loguniform", "Int", "LogInt", "Choice",
           "CHOICE_KEY", "AskTellOptimizer", "Trial",
           "StudyBank", "StudyLedger", "Tuner", "TunerResults",
           "AsyncTuner"]
